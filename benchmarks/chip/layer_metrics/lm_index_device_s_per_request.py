"""Device seconds of what the learned key selection adds to the language
model (the ``lm_index`` class: the indexer's three projections, its key's
norm and rotation, the index scores, the search for the ``topk`` best,
the gather of the keys chosen, the record of the choice) per request: the
class's seconds in one execution of the generate program (the program's
own trace summary) over the requests the execution served (``lm.rows``
over ``lm.executions``).  The index keys' write is ``lm_cache``'s, with
the keys' and values'.  Nothing where the summary has no second in such a
class (every family but this one)."""

from lib.lm_bytes import class_s, per_request


def read(ctx):
    return per_request(ctx, "lm_index_device_s_per_request",
                       class_s(ctx, "lm_index") or None)
