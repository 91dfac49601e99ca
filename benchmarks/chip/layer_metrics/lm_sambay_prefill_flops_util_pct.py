"""The prefill of the decoder-hybrid-decoder as a share of the chip's
bf16 peak: its least FLOPs (``lib/lm_sambay_bytes.py``: the front's
products with a weight over the positions THE PROGRAM COUNTED there,
``lm.prefill_positions``, and the back's over ``lm.cross_positions``, one
a row, never the configuration's ``prompt_tokens``; the recurrence in its
sequential form, ``6 x d_inner x N`` a position a Mamba layer; the BAND
of every window layer and the last position's row of the one cache for
each of its readers, not the square; the head for one position a row)
over the wall seconds of the generate program's ``prefill`` phase
(``account.by_phase.prefill``, its idle stretches too), over 197 TFLOP/s.
A program's utilisation, not a kernel's roofline share; it cannot pass
100 because the count is the least and the seconds are everything.
Nothing where the program counts no cross positions or its summary has no
phase."""

from lib.account import phase_rows
from lib.lm_bytes import say, served
from lib.lm_sambay_bytes import counted, prefill_flops


def read(ctx):
    rows, serves, counts = phase_rows(ctx, "prefill"), served(ctx), \
        counted(ctx)
    if rows is None or serves is None or counts is None \
            or ctx.peaks is None:
        return None
    seconds = sum(rows.values())
    flops = prefill_flops(ctx.config["lm"], counts["prefill_positions"],
                          counts["cross_positions"], serves["program_rows"],
                          serves["prompt"])
    value = 100.0 * flops / seconds / ctx.peaks["bf16_flops_per_s"]
    say("lm_sambay_prefill_flops_util_pct",
        f"{value:.3f} %: {flops / 1e12:.3f} TFLOP a prefill of "
        f"{counts['prefill_positions']:.0f} positions through the front "
        f"and {counts['cross_positions']:.0f} behind it in "
        f"{serves['program_rows']:.3f} rows ({serves['prompt']:.1f} real a "
        f"row) in {seconds:.5f} s ({rows.get('idle', 0.0):.5f} idle)",
        serves)
    return value
