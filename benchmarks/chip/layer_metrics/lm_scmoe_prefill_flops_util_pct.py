"""The prefill of the latent-attention decoder with a shortcut-connected
expert layer and zero-compute experts as a share of the chip's bf16 peak:
its least FLOPs (``lib/lm_scmoe_bytes.py``: the products with a
non-expert weight over the positions THE PROGRAM COUNTED,
``lm.prefill_positions``, never the configuration's ``prompt_tokens``;
the experts over the pairs it routed to experts held here,
``lm.expert_pairs_local_prefill``; attention over the query-key pairs its
masks let through over the eight attentions,
``lm.keys_attended_prefill``, not the square the masked products walk;
nothing for the zero experts; the head for one position a row) over the
wall seconds of the generate program's ``prefill`` phase
(``account.by_phase.prefill``, its idle stretches too), over 197 TFLOP/s.
A program's utilisation, not a kernel's roofline share; it cannot pass
100 because the count is the least and the seconds are everything.
Nothing where the program counts no pairs to zero experts or its summary
has no phase."""

from lib.account import phase_rows
from lib.lm_bytes import say, served
from lib.lm_scmoe_bytes import counted, prefill_flops


def read(ctx):
    rows, serves, counts = phase_rows(ctx, "prefill"), served(ctx), \
        counted(ctx)
    if rows is None or serves is None or counts is None \
            or ctx.peaks is None:
        return None
    seconds = sum(rows.values())
    flops = prefill_flops(
        ctx.config["lm"], counts["prefill_positions"],
        serves["program_rows"], counts["keys_attended_prefill"],
        counts["expert_pairs_local_prefill"])
    value = 100.0 * flops / seconds / ctx.peaks["bf16_flops_per_s"]
    say("lm_scmoe_prefill_flops_util_pct",
        f"{value:.3f} %: {flops / 1e12:.3f} TFLOP a prefill of "
        f"{counts['prefill_positions']:.0f} positions in "
        f"{serves['program_rows']:.3f} rows ({serves['prompt']:.1f} real a "
        f"row; {counts['keys_attended_prefill'] / 1e6:.1f} M query-key "
        f"pairs, {counts['expert_pairs_local_prefill']:.0f} expert pairs "
        f"held here, {counts['expert_pairs_zero_prefill']:.0f} to zero "
        f"experts) in {seconds:.5f} s ({rows.get('idle', 0.0):.5f} idle)",
        serves)
    return value
