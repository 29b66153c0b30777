"""Device time a step in the loop over the passes itself, in a model whose
layers run several times: the operations, forward and transposed, under the
program's `loop_carry` scope and under no other part of the step
(`loop_unitemised_ms.py`'s list: every operation of a pass lies within the
loop under its own part as well, and is that part's). What is left is the
loop's own work: a pass's saved results stacked as the passes go and taken
back in the backward pass, the tied leaves' gradients summed over the
passes, the counters' sums. Nothing to read where the step has no such scope
(a model whose layers run once)."""


def read(ctx):
    from harness import scope_reduce, scope_sum
    from metrics.loop_unitemised_ms import STEP_PARTS
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    parts = tuple(part for part in STEP_PARTS if part != "loop_carry")
    # an operation under two of them counts for the first: the loop last
    return scope_sum.scope_ms(scopes, parts + ("loop_carry",))[
        "loop_carry"] or None
