"""Device time a step in the noising of training by diffusion over blocks:
the operations, forward and transposed, under the program's `bd_noise` scope
(the draws of t and of the masked positions from the state's key, `x_t`, the
doubled row `[x_t ; x_0]`, the loss's weights `m / t`). The line `bench
bd_noise` prints it beside the program's two counters of the noise
(`tpudist.telemetry.counters()`: `bd_masked_share`, masked positions over
rows x L, near `(1 + noise_eps) / 2`; `bd_weight_sum`, the sum of `m / t`
over rows x L, near 1), for the compared steps and as the window's mean; the
reference prints its own on `bench bd_reference`. Nothing to read where the
step has no such scope (a program trained to predict the next id)."""

import json


def _counters(ctx):
    try:
        from tpudist import telemetry
        kept = telemetry.counters()
    except (ImportError, AttributeError):
        return {}
    steps, compared = int(ctx["steps"]), int(ctx["config"]["compared_steps"])
    return {name: {"compared": values[:compared],
                   "window_mean": sum(values[-steps:]) / len(values[-steps:])}
            for name, values in sorted(kept.items())
            if name.startswith("bd_") and values}


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    ms = scope_sum.scope_ms(scopes, ("bd_noise",))["bd_noise"]
    if not ms:
        return None
    print("bench bd_noise " + json.dumps(dict(_counters(ctx), bd_noise_ms=ms)),
          flush=True)
    return ms
