"""A decode step's share of its memory roofline, for the decoder with a
learned key selection and routed experts: the least bytes one step must
move (``lib/lm_dsa_moe_bytes.py``: every resident non-expert weight and
the whole head once, whatever the rows; of the routed experts only those
the step HIT, ``lm.expert_hits``; 128 B for every index key a row SCORED
and 2 KiB for every key it ATTENDED to, as the program counted them
(``lm.keys_scored_decode``, ``lm.keys_attended``: 2,048 a block where the
cache holds 8,200); for each row of the PROGRAM its embedding row and
what it writes) over the chip's HBM peak, over the wall seconds of a step
of the ``decode`` phase (``account.by_phase.decode``, its idle stretches
too: what ``lm_decode_step_ms`` reads).  The counters are the REAL rows';
a padded row's reads are left out, which only lowers the share.  Under
100 by construction: the bytes are the least, the seconds everything.
Nothing where the program counts no index keys or its summary has no
phase."""

from lib.account import phase_rows
from lib.lm_bytes import say, served
from lib.lm_dsa_moe_bytes import DECODE_COUNTERS, counted, \
    decode_bytes_per_step, expert_params, index_key_bytes, key_bytes


def read(ctx):
    rows, serves, counts = phase_rows(ctx, "decode"), served(ctx), \
        counted(ctx, DECODE_COUNTERS)
    if rows is None or serves is None or counts is None \
            or ctx.peaks is None or not serves["steps"]:
        return None
    lm, steps = ctx.config["lm"], serves["steps"]
    scored, attended, hits = (counts[name] / steps for name in (
        "keys_scored_decode", "keys_attended", "expert_hits"))
    nbytes = decode_bytes_per_step(lm, serves["program_rows"], scored,
                                   attended, hits)
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    step_s = sum(rows.values()) / steps
    value = 100.0 * least / step_s
    blocks = lm["num_hidden_layers"] * serves["rows"]
    say("lm_dsa_decode_hbm_roofline_pct",
        f"{value:.3f} %: {nbytes / 1e9:.3f} GB a step ({hits:.2f} experts "
        f"hit x {2 * expert_params(lm) / 1e6:.2f} MB; a row a block "
        f"{scored / blocks:.1f} index keys scored x {index_key_bytes(lm)} "
        f"B and {attended / blocks:.1f} keys attended x {key_bytes(lm)} B)"
        f" -> {1e3 * least:.3f} ms at the HBM peak, against "
        f"{1e3 * step_s:.3f} ms a step of the decode phase", serves)
    return value
