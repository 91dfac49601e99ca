"""The prefill of the decoder with a learned key selection and routed
experts as a share of the chip's bf16 peak: its least FLOPs
(``lib/lm_dsa_moe_bytes.py``: the products with a weight over the
positions THE PROGRAM COUNTED, ``lm.prefill_positions``, never the
configuration's ``prompt_tokens``; the experts over the counted local
pairs; the index scores over the query-key pairs scored,
``lm.keys_scored_prefill``; attention over the keys SELECTED,
``lm.keys_attended_prefill``, not the triangle the masked products walk;
the head for one position a row) over the wall seconds of the generate
program's ``prefill`` phase (``account.by_phase.prefill``, its idle
stretches too), over 197 TFLOP/s.  A program's utilisation, not a
kernel's roofline share; it cannot pass 100 because the count is the
least and the seconds are everything.  Nothing where the program counts
no index keys or its summary has no phase."""

from lib.account import phase_rows
from lib.lm_bytes import say, served
from lib.lm_dsa_moe_bytes import PREFILL_COUNTERS, counted, prefill_flops


def read(ctx):
    rows, serves, counts = phase_rows(ctx, "prefill"), served(ctx), \
        counted(ctx, PREFILL_COUNTERS)
    if rows is None or serves is None or counts is None \
            or ctx.peaks is None:
        return None
    seconds = sum(rows.values())
    flops = prefill_flops(
        ctx.config["lm"], counts["prefill_positions"],
        serves["program_rows"], counts["keys_scored_prefill"],
        counts["keys_attended_prefill"],
        counts["expert_pairs_local_prefill"])
    value = 100.0 * flops / seconds / ctx.peaks["bf16_flops_per_s"]
    say("lm_dsa_prefill_flops_util_pct",
        f"{value:.3f} %: {flops / 1e12:.3f} TFLOP a prefill of "
        f"{counts['prefill_positions']:.0f} positions in "
        f"{serves['program_rows']:.3f} rows ({serves['prompt']:.1f} real a "
        f"row; {counts['keys_scored_prefill'] / 1e6:.1f} M pairs scored, "
        f"{counts['keys_attended_prefill'] / 1e6:.1f} M attended, "
        f"{counts['expert_pairs_local_prefill']:.0f} expert pairs) in "
        f"{seconds:.5f} s ({rows.get('idle', 0.0):.5f} idle)", serves)
    return value
