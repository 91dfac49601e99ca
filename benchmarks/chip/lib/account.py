"""What the readers of a program's ACCOUNT share: the ``account`` the
program's own trace summary (``lib/profile.py`` `summary`) keeps beside a
program's ``classes`` since PR 38, in which every nanosecond of a whole
execution has one owner: ``by_class`` (exclusive seconds an execution by
kernel class, with ``other`` and ``idle``, adding up to the execution's),
``by_phase`` (the same by ``prefill`` / ``decode`` / ``none``, where the
program's scopes carry a phase), ``overlap_s`` and ``dropped_s`` (what a
sum of ``classes`` counts twice, and what it leaves out).

A reader gets None, never 0, where the run has no device trace, the
configuration names no such program, the summary has no ``account`` (a
program from before PR 38) or the program no phase.
"""

from __future__ import annotations

import re

from .flops import request_shape
from .profile import summary
from .server import BenchFailure

# the classes the four accepted denoise readers sum (attn_, proj_ff_,
# conv_, norm_device_s_per_image); ``glue`` is every class beside them
READ_CLASSES = ("attn_self", "attn_cross", "attn_proj", "ff", "resblock",
                "resample", "norm")
OTHER, IDLE = "other", "idle"


def program_row(ctx, key: str) -> dict | None:
    """The summary's row of the one program the configuration's pattern
    ``key`` matches, where it carries an ``account``."""
    prof = summary(ctx)
    if prof is None or key not in ctx.config["programs"]:
        return None
    pattern = re.compile(ctx.config["programs"][key])
    rows = [p for name, p in prof["programs"].items() if pattern.search(name)]
    if len(rows) != 1:
        raise BenchFailure(
            f"{len(rows)} programs of the summary match "
            f"{pattern.pattern!r} ({key}); it has {sorted(prof['programs'])}")
    return rows[0] if "account" in rows[0] else None


def phase_rows(ctx, phase: str) -> dict | None:
    """Exclusive seconds by class of one execution of the generate
    program under the scope ``phase``, its idle stretches too."""
    row = program_row(ctx, "lm_generate")
    if row is None:
        return None
    return row["account"].get("by_phase", {}).get(phase)


def phase_class_s(ctx, phase: str, *classes: str) -> float | None:
    """Exclusive seconds of kernel classes in one execution of the
    generate program under ``phase``; None where they hold no second (a
    model without such a class) as where there is no phase."""
    rows = phase_rows(ctx, phase)
    if rows is None:
        return None
    return sum(rows.get(c, 0.0) for c in classes) or None


def denoise_s_per_image(ctx, metric: str, pick) -> float | None:
    """``pick(by_class)`` seconds of one denoise execution, per image,
    printed beside what the account's rows and the accepted readers'
    add up to: the four accepted groups as ``classes`` has them
    (inclusive), then glue, other and gaps as the account has them
    (exclusive), against the execution's seconds."""
    row = program_row(ctx, "denoise")
    if row is None:
        return None
    batch = request_shape(ctx.config["graph"])["batch_size"]
    account, classes = row["account"], row["classes"]
    by_class = account["by_class"]
    read = sum(classes.get(c, 0.0) for c in READ_CLASSES)
    glue, other, gaps = glue_s(by_class), by_class.get(OTHER, 0.0), \
        by_class[IDLE]
    value = pick(by_class) / batch
    print(f"[chipbench] {metric}: {value:.6f} s an image; attn + proj_ff + "
          f"conv + norm (inclusive, accepted) {read:.6f} + glue {glue:.6f} "
          f"+ other {other:.6f} + gaps {gaps:.6f} (exclusive) = "
          f"{read + glue + other + gaps:.6f} against denoise "
          f"{row['mean_s']:.6f}; overlap_s {account['overlap_s']:.6f}, "
          f"dropped_s {account.get('dropped_s', 0.0):.6f}, the accepted "
          f"classes' gaps {classes.get('gaps', 0.0):.6f}", flush=True)
    return value


def glue_s(by_class: dict) -> float:
    """What the denoise runs that is no attention, matmul, convolution or
    norm: ``sampler``, ``embed`` and every class named since."""
    return sum(s for c, s in by_class.items()
               if c not in READ_CLASSES and c not in (OTHER, IDLE))
