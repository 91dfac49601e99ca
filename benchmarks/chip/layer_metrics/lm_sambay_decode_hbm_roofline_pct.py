"""A decode step's share of its memory roofline, for the
decoder-hybrid-decoder: the least bytes one step must move
(``lib/lm_sambay_bytes.py``: every resident weight once, the tied
embedding once; for each row of the PROGRAM, padded ones too, the nine
states and tails read and written at their stored width, its embedding
row and the keys and values it writes; 5,120 B for every key its real
rows attended to in the rings and in the one cache, ONCE FOR EACH LAYER
THAT READS IT, as the program counted them from the masks its steps
applied) over the chip's HBM peak, over the wall seconds of a step of the
``decode`` phase (``account.by_phase.decode``, its idle stretches too:
what ``lm_decode_step_ms`` reads).  Under 100 by construction: the bytes
are the least, the seconds everything.  Nothing where the program counts
no cross positions or its summary has no phase."""

from lib.account import phase_rows
from lib.lm_bytes import say, served
from lib.lm_sambay_bytes import counted, decode_bytes_per_step, key_bytes, \
    state_bytes_per_row


def read(ctx):
    rows, serves, counts = phase_rows(ctx, "decode"), served(ctx), \
        counted(ctx)
    if rows is None or serves is None or counts is None \
            or ctx.peaks is None or not serves["steps"]:
        return None
    lm, steps = ctx.config["lm"], serves["steps"]
    keys = counts["keys"] / steps
    nbytes = decode_bytes_per_step(lm, serves["program_rows"], keys)
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    step_s = sum(rows.values()) / steps
    value = 100.0 * least / step_s
    say("lm_sambay_decode_hbm_roofline_pct",
        f"{value:.3f} %: {nbytes / 1e9:.3f} GB a step "
        f"({serves['program_rows']:.3f} rows x "
        f"{2 * state_bytes_per_row(lm) / 1e6:.1f} MB of state read and "
        f"written; {keys:.1f} keys and readers x {key_bytes(lm)} B) -> "
        f"{1e3 * least:.3f} ms at the HBM peak, against "
        f"{1e3 * step_s:.3f} ms a step of the decode phase", serves)
    return value
