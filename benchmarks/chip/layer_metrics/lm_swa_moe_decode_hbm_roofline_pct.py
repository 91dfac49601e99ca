"""A decode step's share of its memory roofline, for the decoder with
window and full attention layers and routed experts: the least bytes one
step must move (``lib/lm_swa_moe_bytes.py``: every resident non-expert
weight once and the head's slice, whatever the rows; of the routed
experts only those the step HIT, ``lm.expert_hits``; for each row of the
PROGRAM, padded ones too, its embedding row and 4 KiB for every key it
attended to, as the program counted them from the masks its steps
applied: a ring's live slots and a full layer's positions, not a mean
position) over the chip's HBM peak, over the measured device time of a
step OF THE ``decode`` PHASE ALONE (the prefill of 512 positions is a
third of an execution and is none of a step's).  Nothing where the
program counts no keys or its summary has no phase."""

from lib.lm_bytes import say, served
from lib.lm_moe_bytes import routing
from lib.lm_swa_moe_bytes import attended, decode_bytes_per_step, \
    expert_params, key_bytes, phase_s


def read(ctx):
    seconds, serves, routed = phase_s(ctx, "decode"), served(ctx), \
        routing(ctx)
    if seconds is None or serves is None or routed is None \
            or ctx.peaks is None:
        return None
    keys = attended(ctx, serves)
    if keys is None:
        return None
    lm, steps = ctx.config["lm"], serves["steps"]
    nbytes = decode_bytes_per_step(lm, keys, serves["program_rows"],
                                   routed["hits"])
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    value = 100.0 * least / (seconds / steps)
    say("lm_swa_moe_decode_hbm_roofline_pct",
        f"{value:.3f} %: {nbytes / 1e9:.3f} GB a step "
        f"({routed['hits']:.3f} experts hit x "
        f"{2 * expert_params(lm) / 1e6:.1f} MB; {keys:.1f} keys a row x "
        f"{key_bytes(lm)} B; {routed['local']:.3f} of "
        f"{routed['pairs']:.3f} pairs local, {routed['dropped']} dropped) "
        f"-> {1e3 * least:.3f} ms at the HBM peak, against "
        f"{1e3 * seconds / steps:.3f} ms a step of the decode phase", serves)
    return value
