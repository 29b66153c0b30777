"""Device time a step in a language model's output head and its loss: the
operations, forward and transposed, under the program's `lm_head` scope (the
head's product, a chunk of positions at a time) or its `tpudist_loss` scope
(the cross entropy of the chunk). The embedding's time (`lm_embed`) is
printed beside it on `bench lm_head_ms`, not counted. Nothing to read where
the step has no `lm_head` scope (a classifier)."""

import json


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    parts = scope_sum.scope_ms(scopes,
                               ("lm_head", "tpudist_loss", "lm_embed"))
    if not parts["lm_head"]:
        return None
    total = parts["lm_head"] + parts["tpudist_loss"]
    print("bench lm_head_ms " + json.dumps(dict(parts, lm_head_ms=total)),
          flush=True)
    return total
