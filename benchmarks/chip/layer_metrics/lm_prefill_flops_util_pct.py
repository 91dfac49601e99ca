"""The language model's prefill as a share of the chip's bf16 peak: its
least FLOPs (``lib/lm_swa_moe_bytes.py``: the non-expert products over
every position of the program's rows; the experts over the LOCAL pairs
the program counted, ``lm.expert_pairs_local_prefill``; attention over
the keys each query may see, the band of a sliding layer and not the
square; the head for one position a row) over the seconds of the
generate program's ``prefill`` phase, over 197 TFLOP/s.  A program's
utilisation, not a kernel's roofline share; it cannot pass 100 because
the count is the least.  Nothing where the program counts no such pairs
or its summary has no phase."""

from lib.lm_bytes import say, served
from lib.lm_swa_moe_bytes import phase_s, prefill_flops


def read(ctx):
    seconds, serves = phase_s(ctx, "prefill"), served(ctx)
    counters = ctx.metrics_window["pipeline"]["counters"]
    if seconds is None or serves is None or ctx.peaks is None \
            or "lm.expert_pairs_local_prefill" not in counters:
        return None
    nodes = {n["class_type"]: n["inputs"]
             for n in ctx.config["graph"].values()}
    positions = nodes["LanguageModelGenerate"]["prompt_tokens"]
    pairs = counters["lm.expert_pairs_local_prefill"] \
        / counters["lm.executions"]
    flops = prefill_flops(ctx.config["lm"], serves["program_rows"],
                          positions, serves["prompt"], pairs)
    value = 100.0 * flops / seconds / ctx.peaks["bf16_flops_per_s"]
    say("lm_prefill_flops_util_pct",
        f"{value:.3f} %: {flops / 1e12:.3f} TFLOP a prefill of "
        f"{serves['program_rows']:.3f} rows x {positions} positions "
        f"({serves['prompt']:.1f} real; {pairs:.0f} local pairs) in "
        f"{seconds:.5f} s", serves)
    return value
