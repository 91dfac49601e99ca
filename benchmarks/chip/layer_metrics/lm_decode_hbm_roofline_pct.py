"""A decode step's share of its memory roofline: the least bytes one
token must move (``lib/lm_bytes.py``: the layers' weights once per loop,
the head, and the cache up to the mean position a step of this window
attends to) over the chip's HBM peak, over the measured device time per
token.  Memory-bound by construction: at batch 1 each weight is used
once.  The measured time holds the prefill too, so the share errs low."""

from lib.lm_bytes import decode_bytes_per_token, per_request, program_s


def read(ctx):
    seconds, tokens = program_s(ctx), per_request(ctx, "lm.tokens_decoded")
    if seconds is None or not tokens or ctx.peaks is None:
        return None
    prompt = per_request(ctx, "lm.prompt_tokens") or 0.0
    nbytes = decode_bytes_per_token(ctx.config["lm"],
                                    prompt + (tokens - 1) / 2.0)
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    print(f"[chipbench] lm_decode_hbm_roofline_pct: {nbytes / 1e9:.3f} GB a "
          f"token -> {1e3 * least:.3f} ms at the HBM peak, against "
          f"{1e3 * seconds / tokens:.3f} ms measured", flush=True)
    return 100.0 * least / (seconds / tokens)
