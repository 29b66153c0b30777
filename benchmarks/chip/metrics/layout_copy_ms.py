"""Device time a step in instructions the compiler added with no name of the
program's at all (no HLO metadata: layout `copy.N`, `copy-start` /
`copy-done`), kept apart from the phases. `bench scope_ms` says what they are
(`layout_copy_opcodes_ms`) and behind which phase's operations they were
added (`layout_copy_behind_ms`). With `fwd_ms`, `bwd_ms` and `opt_ms` it sums
to a step's busy time (less `unscoped`, which `step_scopes` holds under 1 %)."""


def read(ctx):
    from harness import scope_reduce
    scopes = scope_reduce.step_scopes(ctx)
    return None if scopes is None else scopes["phase_ms"]["layout_copy"]
