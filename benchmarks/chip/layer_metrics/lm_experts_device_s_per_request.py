"""Device seconds of what routing adds to the language model (the
``lm_experts`` class: the router, the dispatch, the routed experts this
chip holds, the combine) per request: the class's seconds in one
execution of the program over the requests the execution served
(``lm.rows`` over ``lm.executions``).  Nothing where the program's
summary has no second in such a class (a model without experts)."""

from lib.lm_bytes import class_s, per_request


def read(ctx):
    return per_request(ctx, "lm_experts_device_s_per_request",
                       class_s(ctx, "lm_experts") or None)
