"""A decode step's share of its memory roofline, for the decoder with
routed experts: the least bytes one step must move
(``lib/lm_moe_bytes.py``: every resident non-expert weight of the blocks
held once and the head's slice, whatever the rows; for each row of the
PROGRAM, padded ones too, its embedding row and the LATENT cache up to
the mean position a step of this window attends to; of the routed
experts only those the step HIT, ``lm.expert_hits`` over the window's
decode steps) over the chip's HBM peak, over the measured device time of
a step.  Memory-bound by construction: with a few rows each weight is
still used once a step.  The measured time holds the prefill too, so the
share errs low; a program that streams experts nobody chose reads lower
still.  Nothing where the program counts no routing."""

from lib.lm_bytes import program_s, say, served
from lib.lm_moe_bytes import decode_bytes_per_step, expert_params, routing


def read(ctx):
    seconds, serves, routed = program_s(ctx), served(ctx), routing(ctx)
    if seconds is None or serves is None or routed is None \
            or ctx.peaks is None:
        return None
    lm, steps = ctx.config["lm"], serves["steps"]
    nbytes = decode_bytes_per_step(
        lm, serves["prompt"] + (steps - 1) / 2.0, serves["program_rows"],
        routed["hits"])
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    value = 100.0 * least / (seconds / steps)
    say("lm_moe_decode_hbm_roofline_pct",
        f"{value:.3f} %: {nbytes / 1e9:.3f} GB a step "
        f"({routed['hits']:.3f} experts hit x "
        f"{2 * expert_params(lm) / 1e6:.1f} MB; {routed['local']:.3f} of "
        f"{routed['pairs']:.3f} pairs local, {routed['dropped']} dropped) "
        f"-> {1e3 * least:.3f} ms at the HBM peak, against "
        f"{1e3 * seconds / steps:.3f} ms measured", serves)
    return value
