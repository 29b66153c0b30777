"""Device time a step in what latent attention adds around its kernels: the
operations, forward and transposed, under the program's `mla_down` (hidden ->
the two latents and the rotated key's columns), `mla_latent_norm` (the
RMSNorm inside each low-rank path) and `mla_up` (latents -> heads) scopes.
The line `bench mla_latent_ms` prints the three. Nothing to read where the
step has no such scope (attention that projects q, k and v whole)."""

import json

PARTS = ("mla_down", "mla_latent_norm", "mla_up")


def read(ctx):
    from harness import scope_reduce, scope_sum
    scopes = scope_reduce.step_scopes(ctx)
    if scopes is None:
        return None
    parts = scope_sum.scope_ms(scopes, PARTS)
    total = sum(parts.values())
    if not total:
        return None
    print("bench mla_latent_ms " + json.dumps(dict(parts, mla_latent_ms=total)),
          flush=True)
    return total
