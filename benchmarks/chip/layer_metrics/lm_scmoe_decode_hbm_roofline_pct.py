"""A decode step's share of its memory roofline, for the latent-attention
decoder with a shortcut-connected expert layer and zero-compute experts:
the least bytes one step must move (``lib/lm_scmoe_bytes.py``: every
resident NON-expert weight of the layers held once and the head's slice,
whatever the rows; of the routed experts only those the step HIT,
``lm.expert_hits`` over the window's decode steps; NOTHING for the zero
experts; for each row of the PROGRAM, padded ones too, its embedding row
and the eight latents it writes; 1,152 B for every key its real rows
attended to over the eight attentions, as the program counted them from
the masks its steps applied, ``lm.keys_attended``) over the chip's HBM
peak, over the wall seconds of a step of the ``decode`` phase
(``account.by_phase.decode``, its idle stretches too: what
``lm_decode_step_ms`` reads).  Under 100 by construction: the bytes are
the least, the seconds everything.  Nothing where the program counts no
pairs to zero experts or its summary has no phase."""

from lib.account import phase_rows
from lib.lm_bytes import say, served
from lib.lm_scmoe_bytes import counted, decode_bytes_per_step, \
    expert_params, latent_bytes


def read(ctx):
    rows, serves, counts = phase_rows(ctx, "decode"), served(ctx), \
        counted(ctx)
    if rows is None or serves is None or counts is None \
            or ctx.peaks is None or not serves["steps"]:
        return None
    lm, steps = ctx.config["lm"], serves["steps"]
    keys, hits = counts["keys_attended"] / steps, \
        counts["expert_hits"] / steps
    nbytes = decode_bytes_per_step(lm, serves["program_rows"], keys, hits)
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    step_s = sum(rows.values()) / steps
    value = 100.0 * least / step_s
    say("lm_scmoe_decode_hbm_roofline_pct",
        f"{value:.3f} %: {nbytes / 1e9:.3f} GB a step ({hits:.3f} experts "
        f"hit x {2 * expert_params(lm) / 1e6:.1f} MB; of "
        f"{counts['expert_pairs'] / steps:.1f} pairs "
        f"{counts['expert_pairs_local'] / steps:.3f} local and "
        f"{counts['expert_pairs_zero'] / steps:.3f} to zero experts, which "
        f"move nothing; {keys:.1f} keys x {latent_bytes(lm)} B) -> "
        f"{1e3 * least:.3f} ms at the HBM peak, against "
        f"{1e3 * step_s:.3f} ms a step of the decode phase", serves)
    return value
