"""Seconds of set-up spent tracing programs, lowering them, compiling them
and loading compiled ones from the persistent cache: ``retraces.trace_s``
+ ``lower_s`` + ``compile_s`` + ``cache_load_s`` at window start.  Prints
the four apart."""


def read(ctx):
    r = ctx.metrics_setup["retraces"]
    if "trace_s" not in r:
        return None
    print(f"[chipbench] setup_trace_compile_s: trace {r['trace_s']:.1f} s "
          f"({r['traces']}), compile {r['compile_s']:.1f} s "
          f"({r['compiles_uncached']} uncached), cache load "
          f"{r['cache_load_s']:.1f} s ({r['cache_loads']}), lowering "
          f"{r['lower_s']:.1f} s", flush=True)
    return r["trace_s"] + r["lower_s"] + r["compile_s"] + r["cache_load_s"]
