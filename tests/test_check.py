"""tpudist-check (tpudist/analysis + tpudist/check): the static-analysis
gate, provable without jax — every rule against a positive AND negative
fixture, pragma/baseline semantics, the JSON CI surface, the exit-code
contract, and the repo-wide clean run that tier-1 gates on.

The acceptance shape (ISSUE 7): the committed tree exits 0, and seeding
any ONE of the six hazard classes flips the gate nonzero — pinned here per
rule family, plus the smoke-script e2e.

No jax import anywhere in this module (and none inside the analyzer — the
clean-run test asserts that too): the checker must run in environments
where jax is broken or absent, e.g. the launcher's supervisor image.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tpudist.analysis import core

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Declares a mesh axis so fixtures only trip the rule under test, never a
# collateral COLL02.
_AXIS_PREAMBLE = 'DATA_AXIS = "data"\n'


def run_on(tmp_path, source, name="fixture.py", rules=None, root=REPO):
    """Analyze one fixture file against the repo root (the root supplies
    the real telemetry schema); returns the finding list."""
    path = tmp_path / name
    path.write_text(_AXIS_PREAMBLE + textwrap.dedent(source))
    findings, _ = core.run_check(root, paths=[str(path)], rules=rules)
    return findings


def rule_ids(findings, unsuppressed_only=True):
    return [f.rule for f in findings
            if not (unsuppressed_only and f.suppressed)]


# -- TRACE01/02: trace purity ------------------------------------------------

def test_trace_purity_positive(tmp_path):
    findings = run_on(tmp_path, """
        import time
        import numpy as np
        import jax


        def step(state, batch):
            t0 = time.time()
            noise = np.random.normal()
            print("hello", t0)
            v = batch.item()
            return state + noise + v


        train_step = jax.jit(step, donate_argnums=())
        """)
    msgs = [f.message for f in findings if f.rule == "TRACE01"]
    assert len(msgs) == 4, findings
    assert any("time" in m for m in msgs)
    assert any("HOST RNG" in m for m in msgs)
    assert any("jax.debug.print" in m for m in msgs)
    assert any("ConcretizationTypeError" in m for m in msgs)


def test_trace_purity_reaches_through_helpers_and_partial(tmp_path):
    """The hazard sits two hops from the jit: step -> partial(loss_fn) ->
    helper. All three edges (direct call, partial alias, plain call) must
    resolve."""
    findings = run_on(tmp_path, """
        import time
        from functools import partial
        import jax


        def helper(x):
            return x * time.time()


        def loss_fn(scale, x):
            return helper(x) * scale


        def step(x):
            lf = partial(loss_fn, 2.0)
            return lf(x)


        train_step = jax.jit(step)
        """)
    assert rule_ids(findings) == ["TRACE01"]


def test_trace_purity_negative_host_code_and_callbacks(tmp_path):
    """Host-side clocks are fine; so is a host function passed to
    jax.pure_callback (the sanctioned escape hatch); so is
    jax.debug.print."""
    findings = run_on(tmp_path, """
        import time
        import jax


        def host_log(x):
            print("loss", x, time.time())


        def step(x):
            jax.debug.print("x={x}", x=x)
            jax.pure_callback(host_log, None, x)
            return x + 1


        train_step = jax.jit(step)


        def hot_loop(xs):
            t0 = time.time()          # host code: not reachable from a trace
            for x in xs:
                train_step(x)
            return time.time() - t0
        """)
    assert rule_ids(findings) == []


def test_trace_closure_mutation(tmp_path):
    findings = run_on(tmp_path, """
        import jax


        def make_step():
            n = 0

            def step(x):
                nonlocal n
                n += 1
                return x + n

            return jax.jit(step)
        """)
    assert rule_ids(findings) == ["TRACE02"]


def test_flax_module_call_is_traced(tmp_path):
    """flax __call__ bodies execute under model.apply inside the jitted
    step — the dynamic dispatch a call graph can't see, special-cased."""
    findings = run_on(tmp_path, """
        import numpy as np
        from flax import linen as nn


        class Block(nn.Module):
            def __call__(self, x):
                return x + np.random.uniform()
        """)
    assert rule_ids(findings) == ["TRACE01"]


# -- COLL01/02: collective symmetry ------------------------------------------

def test_rank_guarded_collective(tmp_path):
    findings = run_on(tmp_path, """
        import jax


        def step(x, rank):
            if rank == 0:
                x = jax.lax.psum(x, "data")
            return x
        """)
    assert rule_ids(findings) == ["COLL01"]


def test_rank_guarded_barrier_via_is_primary(tmp_path):
    findings = run_on(tmp_path, """
        from tpudist import dist


        def save(path):
            if dist.is_primary():
                write(path)
                dist.barrier("saved")
        """)
    assert rule_ids(findings) == ["COLL01"]


def test_early_exit_then_collective(tmp_path):
    """The shape the lexical check alone would miss: non-primary ranks
    return before reaching the barrier."""
    findings = run_on(tmp_path, """
        from tpudist import dist


        def save(path):
            if not dist.is_primary():
                return
            write(path)
            dist.barrier("saved")
        """)
    assert rule_ids(findings) == ["COLL01"]


def test_guard_and_collective_inside_one_loop_body(tmp_path):
    """The in-train-loop variant of the deadlock shape: guard and
    collective live inside ONE compound statement, so top-level statement
    ordering alone would miss it."""
    findings = run_on(tmp_path, """
        import jax


        def train(loader, rank):
            for batch in loader:
                if rank == 0:
                    continue
                jax.lax.psum(batch, "data")


        def wait(rank):
            while True:
                if rank != 0:
                    return
                jax.lax.pmean(1.0, "data")
        """)
    assert rule_ids(findings) == ["COLL01", "COLL01"]


def test_symmetric_patterns_are_clean(tmp_path):
    """process_count is identical on every rank (symmetric conditional);
    guard-the-write-then-barrier-outside is the sanctioned pattern."""
    findings = run_on(tmp_path, """
        import jax
        from tpudist import dist


        def save(path):
            if dist.is_primary():
                write(path)
            dist.barrier("saved")


        def maybe_sync(tag):
            if jax.process_count() == 1:
                return
            dist.barrier(tag)
        """)
    assert rule_ids(findings) == []


def test_nested_scope_guard_does_not_poison_outer(tmp_path):
    """A rank-dependent early exit inside a NESTED def is that scope's
    business — a collective later in the OUTER scope is symmetric and
    must not flag."""
    findings = run_on(tmp_path, """
        from tpudist import dist


        def save(path):
            def primary_only():
                if not dist.is_primary():
                    return None
                return path

            write(primary_only())
            dist.barrier("saved")
        """)
    assert rule_ids(findings) == []


def test_unknown_axis_name(tmp_path):
    findings = run_on(tmp_path, """
        import jax


        def step(x):
            return jax.lax.pmean(x, axis_name="dta")
        """)
    assert rule_ids(findings) == ["COLL02"]
    assert "dta" in findings[0].message


def test_declared_axes_are_clean(tmp_path):
    """Axes declared via Mesh tuples, P specs, shard_map kwargs, and
    *_axis defaults all count. (The seq mesh exists because SHARD01 holds
    P entries to the stricter mesh-declared set — COLL02's P-declares-axis
    harvest is pinned separately below with a restricted-rules run.)"""
    findings = run_on(tmp_path, """
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(devs(), ("data", "model"))
        mesh_seq = Mesh(devs(), ("seq",))
        spec = P("seq")


        def step(x, data_axis="data"):
            a = jax.lax.pmean(x, axis_name="model")
            b = jax.lax.psum(x, "seq")
            return a + b
        """)
    assert rule_ids(findings) == []


def test_partitionspec_still_declares_axes_for_coll02(tmp_path):
    """A P spec entry declares its axis for COLL02 purposes even when no
    mesh names it (collectives inside shard_map bodies reference axes the
    in_specs mention) — only SHARD01 applies the stricter mesh-declared
    rule, pinned by the restricted run here."""
    findings = run_on(tmp_path, """
        import jax
        from jax.sharding import PartitionSpec as P

        spec = P("seq")


        def inner(x):
            return jax.lax.psum(x, "seq")
        """, rules={"COLL02"})
    assert rule_ids(findings) == []


# -- DONATE01: donation safety -----------------------------------------------

def test_donated_buffer_read_after_call(tmp_path):
    findings = run_on(tmp_path, """
        import jax


        def run(state, batch):
            step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))
            new_state = step(state, batch)
            return state.mean()
        """)
    assert rule_ids(findings) == ["DONATE01"]
    assert "donate" in findings[0].message


def test_donated_jit_default_argnum_zero(tmp_path):
    """This repo's choke point donates argnum 0 by default."""
    findings = run_on(tmp_path, """
        from tpudist.parallel._common import donated_jit


        def run(state, batch):
            step = donated_jit(lambda s, b: s + b)
            out = step(state, batch)
            return state
        """)
    assert rule_ids(findings) == ["DONATE01"]


def test_rebind_pattern_is_clean(tmp_path):
    """state = step(state, ...) — the canonical loop shape — never flags,
    including the self.state attribute form the Trainer uses."""
    findings = run_on(tmp_path, """
        import jax


        def run(state, batches):
            step = jax.jit(lambda s, b: (s + b, s.mean()),
                           donate_argnums=(0,))
            for b in batches:
                state, metrics = step(state, b)
            return state


        class T:
            def fit(self, batches):
                self.train_step = jax.jit(lambda s, b: (s, 0.0),
                                          donate_argnums=(0,))
                for b in batches:
                    self.state, m = self.train_step(self.state, b)
                return self.state
        """)
    assert rule_ids(findings) == []


def test_reassignment_before_read_is_clean(tmp_path):
    findings = run_on(tmp_path, """
        import jax


        def run(state, batch):
            step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))
            out = step(state, batch)
            state = fresh()
            return state.mean()
        """)
    assert rule_ids(findings) == []


# -- PALLAS01: lazy-Pallas discipline ----------------------------------------

def test_module_level_pallas_import(tmp_path):
    findings = run_on(tmp_path, """
        from jax.experimental import pallas as pl
        from tpudist.ops.pallas import flash_attention
        import tpudist.ops.pallas.grouped_matmul
        """)
    assert rule_ids(findings) == ["PALLAS01"] * 3


def test_lazy_and_type_checking_pallas_imports_are_clean(tmp_path):
    findings = run_on(tmp_path, """
        from typing import TYPE_CHECKING

        if TYPE_CHECKING:
            from tpudist.ops.pallas import flash_attention


        def kernel_path(q, k, v):
            from tpudist.ops.pallas import flash_attention as fa
            return fa.flash_attention(q, k, v)
        """)
    assert rule_ids(findings) == []


def test_relative_pallas_import_is_caught(tmp_path):
    """The natural relative refactor of a dispatch client must not evade
    the gate: `from .pallas import ...` in tpudist/ops/ IS a Pallas
    import; the kernel package's own relative imports stay exempt."""
    root = tmp_path / "tree"
    ops = root / "tpudist" / "ops"
    (ops / "pallas").mkdir(parents=True)
    (ops / "client.py").write_text(
        "from .pallas import flash_attention\n")
    (ops / "pallas" / "kernel.py").write_text(
        "from . import flash_attention\n"
        "from jax.experimental import pallas as pl\n")
    findings, _ = core.run_check(str(root), rules={"PALLAS01"})
    assert [(f.rule, f.path) for f in findings] \
        == [("PALLAS01", "tpudist/ops/client.py")]


def test_pallas_package_itself_is_exempt():
    """The kernel package may import Pallas at module level — that's its
    job. Pinned against the real tree, not a fixture."""
    target = os.path.join(REPO, "tpudist", "ops", "pallas",
                          "flash_attention.py")
    findings, _ = core.run_check(REPO, paths=[target],
                                 rules={"PALLAS01"})
    assert rule_ids(findings) == []


# -- TELEM01/02/03: telemetry schema sync ------------------------------------

def test_unknown_event_type(tmp_path):
    findings = run_on(tmp_path, """
        def report(tel):
            tel.emit("step_completed", step=3)
        """)
    assert rule_ids(findings) == ["TELEM01"]


def test_missing_required_fields(tmp_path):
    findings = run_on(tmp_path, """
        def report(tel):
            tel.emit("epoch", epoch=2)
        """)
    assert rule_ids(findings) == ["TELEM02"]
    assert "seconds" in findings[0].message


def test_valid_and_dynamic_emits_are_clean(tmp_path):
    """Schema-complete literal emits pass; dynamic types and **splats are
    the runtime validator's jurisdiction, not lint's."""
    findings = run_on(tmp_path, """
        def report(tel, et, fields):
            tel.emit("fault", point="x", detail="why")
            tel.emit("epoch", epoch=2, seconds=1.5, extra="fine")
            tel.emit(et, anything=1)
            tel.emit("step", **fields)
        """)
    assert rule_ids(findings) == []


def test_schema_docs_sync_rule_fires_on_drift(tmp_path):
    """TELEM03 against a synthetic root: telemetry.py declares an event
    the docs never mention."""
    root = tmp_path / "tree"
    (root / "tpudist").mkdir(parents=True)
    (root / "docs").mkdir()
    (root / "tpudist" / "telemetry.py").write_text(textwrap.dedent("""
        SCHEMA = {
            "step": ("step",),
            "ghost_event": ("x",),
        }
        """))
    (root / "docs" / "OBSERVABILITY.md").write_text(
        "| step events | trainer |\n")
    findings, _ = core.run_check(str(root))
    telem3 = [f for f in findings if f.rule == "TELEM03"]
    assert len(telem3) == 1 and "ghost_event" in telem3[0].message
    assert telem3[0].severity == "warning"


# -- RECOMP01/02: recompile hazards ------------------------------------------

def test_jit_in_loop(tmp_path):
    findings = run_on(tmp_path, """
        import jax


        def sweep(xs):
            for x in xs:
                f = jax.jit(lambda v: v + 1)
                f(x)
        """)
    assert rule_ids(findings) == ["RECOMP01"]


def test_loop_varying_scalar_into_jit(tmp_path):
    findings = run_on(tmp_path, """
        import jax

        step = jax.jit(lambda s, lr: s * lr)


        def fit(state, n):
            for i in range(n):
                state = step(state, 0.1 * (1 - i / n))
            return state
        """)
    assert rule_ids(findings) == ["RECOMP02"]
    assert findings[0].severity == "warning"


def test_hoisted_jit_and_array_args_are_clean(tmp_path):
    """The repo's own conventions: jit built once outside the loop, and
    loop-varying values crossing the boundary as jnp arrays."""
    findings = run_on(tmp_path, """
        import jax
        import jax.numpy as jnp

        step = jax.jit(lambda s, lr: s * lr)


        def fit(state, lrs):
            for lr in lrs:
                state = step(state, jnp.asarray(lr * 2.0, jnp.float32))
            return state
        """)
    assert rule_ids(findings) == []


def test_serving_loop_len_keyed_jit_fires(tmp_path):
    """ISSUE 14: the serving request loop's hazard — a jitted step keyed
    on ``len(batch)`` inside the ``while`` pump compiles a fresh program
    per distinct request-batch size, under live traffic. RECOMP02 covers
    it (loop-variable analysis alone cannot: a ``while True`` pump has no
    loop variable)."""
    findings = run_on(tmp_path, """
        import jax

        step = jax.jit(lambda imgs, n: imgs[:n])


        def serve(queue, imgs):
            while queue:
                batch = queue.pop()
                step(imgs, len(batch))
        """)
    assert rule_ids(findings) == ["RECOMP02"]
    assert "len()" in findings[0].message


def test_loop_invariant_len_is_clean(tmp_path):
    """len() of a collection bound OUTSIDE the loop is one value — one
    compile-cache key, one compile. The serving extension must not flag
    it (only a loop-varying operand is the per-iteration hazard)."""
    findings = run_on(tmp_path, """
        import jax

        step = jax.jit(lambda imgs, n: imgs[:n])


        def fit(imgs, class_names, epochs):
            for _ in range(epochs):
                step(imgs, len(class_names))
        """)
    assert rule_ids(findings) == []


def test_serving_loop_bucket_quantized_is_clean(tmp_path):
    """The sanctioned fix: sizes quantized through the serve bucket
    helpers take at most len(buckets) distinct values, all AOT-compiled
    at startup — the crossing is recompile-safe and RECOMP02 stands
    down (same for the .shape-arithmetic form)."""
    findings = run_on(tmp_path, """
        import jax

        from tpudist.serve.batching import pad_to_bucket, pick_bucket

        step = jax.jit(lambda imgs: imgs)
        BUCKETS = (1, 2, 4, 8)


        def serve(queue):
            while queue:
                batch = queue.pop()
                step(pad_to_bucket(batch, pick_bucket(len(batch), BUCKETS)))
        """)
    assert rule_ids(findings) == []


def test_serving_loop_shape_arith_fires_in_while(tmp_path):
    """.shape-derived Python arithmetic keys the jitted call inside a
    ``while`` pump — the non-bucketed padding shape (RECOMP02's training
    form, proven on the serving loop's statement shape)."""
    findings = run_on(tmp_path, """
        import jax

        step = jax.jit(lambda imgs, n: imgs)


        def serve(queue):
            while queue:
                batch = queue.pop()
                step(batch, batch.shape[0] + 1)
        """)
    assert rule_ids(findings) == ["RECOMP02"]


# -- NUM01: per-step host syncs in the hot loop ------------------------------

def test_num01_float_on_metric_in_loader_loop_fires(tmp_path):
    findings = run_on(tmp_path, """
        def run(train_loader, meters, step_fn, state):
            for i, (images, labels) in enumerate(train_loader):
                state, metrics = step_fn(state, images, labels)
                meters.update(float(metrics["loss"]))     # blocking sync
                got = jax.device_get(metrics)             # ditto
        """)
    assert rule_ids(findings).count("NUM01") == 2


def test_num01_item_and_block_until_ready_fire_in_hot_funcs(tmp_path):
    findings = run_on(tmp_path, """
        class T:
            def train_epoch(self, batches, step_fn, state):
                for images, labels in batches:
                    state, m = step_fn(state, images, labels)
                    loss = m["loss"].item()
                    m["acc"].block_until_ready()
        """)
    assert rule_ids(findings).count("NUM01") == 2


def test_num01_metadata_and_drain_pattern_are_clean(tmp_path):
    findings = run_on(tmp_path, """
        import time

        class Drain:
            def _apply(self, entries, meters):
                # Sanctioned sink: separate scope, entries already landed.
                for metrics, n in entries:
                    meters.update(float(metrics["loss"]), n)

        def train_epoch(self, train_loader, step_fn, state, drain):
            end = time.time()
            for i, (images, labels) in enumerate(train_loader):
                n = int(images.shape[0])          # metadata: not a sync
                state, metrics = step_fn(state, images, labels)
                drain.push(metrics, n)
                dt = float(time.time() - end)     # host arithmetic: clean
                end = time.time()
        """)
    assert "NUM01" not in rule_ids(findings)


def test_num01_ignores_non_pipeline_loops(tmp_path):
    findings = run_on(tmp_path, """
        def bench(step_fn, state, batch):
            for _ in range(10):
                out = step_fn(state, *batch)
                out.block_until_ready()           # bench timing: not a
            return out                            # loader-iterating loop
        """)
    assert "NUM01" not in rule_ids(findings)


# -- pragma + baseline semantics ---------------------------------------------

def test_pragma_suppresses_with_reason(tmp_path):
    findings = run_on(tmp_path, """
        import jax


        def step(x, rank):
            if rank == 0:
                # tpudist: ignore[COLL01] — single-rank eval path, peers never enter step
                x = jax.lax.psum(x, "data")
            return x
        """)
    assert rule_ids(findings) == []           # nothing unsuppressed
    sup = [f for f in findings if f.suppressed]
    assert len(sup) == 1 and sup[0].rule == "COLL01"
    assert "single-rank" in sup[0].suppress_reason


def test_pragma_without_reason_warns(tmp_path):
    findings = run_on(tmp_path, """
        import jax


        def step(x, rank):
            if rank == 0:
                x = jax.lax.psum(x, "data")  # tpudist: ignore[COLL01]
            return x
        """)
    assert rule_ids(findings) == ["PRAGMA01"]


def test_stale_pragma_warns(tmp_path):
    findings = run_on(tmp_path, """
        x = 1  # tpudist: ignore[TRACE01] — nothing here fires this rule
        """)
    assert rule_ids(findings) == ["PRAGMA02"]


def test_pragma_examples_in_docstrings_are_inert(tmp_path):
    """A pragma EXAMPLE inside a string literal is documentation, not
    suppression — the tokenizer-based scan must not see it."""
    findings = run_on(tmp_path, '''
        DOC = """use  # tpudist: ignore[TRACE01] — like this"""
        ''')
    assert rule_ids(findings) == []


def test_baseline_gates_only_new_findings(tmp_path):
    src = """
        import jax


        def step(x, rank):
            if rank == 0:
                x = jax.lax.psum(x, "data")
            return x
        """
    findings = run_on(tmp_path, src)
    assert core.gate(findings, baseline=set()) != []
    base = tmp_path / "base.json"
    core.write_baseline(str(base), findings)
    assert core.gate(findings, core.load_baseline(str(base))) == []
    # A second hazard in the same file is NEW even though the old one
    # moved lines (content-addressed fingerprints).
    findings2 = run_on(tmp_path, """
        import jax

        PAD = 1


        def step(x, rank):
            if rank == 0:
                x = jax.lax.psum(x, "data")
            return x


        def step2(y, rank):
            if rank == 0:
                y = jax.lax.pmean(y, "data")
            return y
        """)
    new = core.gate(findings2, core.load_baseline(str(base)))
    assert len(new) == 1 and "pmean" in new[0].message


def test_strict_gates_warnings(tmp_path):
    findings = run_on(tmp_path, """
        x = 1  # tpudist: ignore[TRACE01] — stale on purpose
        """)
    assert core.gate(findings, set()) == []
    assert [f.rule for f in core.gate(findings, set(), strict=True)] \
        == ["PRAGMA02"]


# -- CLI: JSON golden + exit codes -------------------------------------------

def _cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tpudist.check", *args],
                          cwd=cwd, capture_output=True, text=True,
                          timeout=300)


def test_json_output_golden(tmp_path):
    """The CI surface: stable shape, the seeded finding carried with rule/
    severity/path/line/fingerprint, exit mirrored in the payload."""
    haz = tmp_path / "haz.py"
    haz.write_text(_AXIS_PREAMBLE + textwrap.dedent("""
        import jax


        def step(x, rank):
            if rank == 0:
                x = jax.lax.psum(x, "data")
            return x
        """))
    r = _cli("--json", "--no-baseline", str(haz))
    assert r.returncode == 1, r.stderr
    obj = json.loads(r.stdout)
    assert sorted(obj) == ["baseline", "counts", "exit", "files",
                           "findings", "new", "root", "unparseable",
                           "version"]
    assert obj["version"] == 1 and obj["exit"] == 1 and obj["files"] == 1
    assert obj["counts"] == {"errors": 1, "warnings": 0, "suppressed": 0,
                             "new": 1}
    (f,) = obj["findings"]
    assert f["rule"] == "COLL01" and f["severity"] == "error"
    assert f["path"].endswith("haz.py") and f["line"] == 8
    assert f["fingerprint"] and obj["new"] == [f["fingerprint"]]


def test_cli_exit_codes(tmp_path):
    assert _cli("--rules", "NOSUCH").returncode == 2
    assert _cli("--list-rules").returncode == 0
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert _cli("--no-baseline", str(clean)).returncode == 0


def test_unparseable_target_cannot_certify(tmp_path):
    """A target the analyzer cannot parse (conflict markers, a directory
    argument) must never yield a green gate — exit 2, in text, json, and
    --write-baseline modes alike."""
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    r = _cli("--no-baseline", str(bad))
    assert r.returncode == 2 and "could not parse" in r.stderr
    r = _cli("--no-baseline", "--json", str(bad))
    assert r.returncode == 2
    assert json.loads(r.stdout)["exit"] == 2
    assert _cli("--no-baseline", str(tmp_path)).returncode == 2  # a dir
    r = _cli("--write-baseline", "--baseline",
             str(tmp_path / "b.json"), str(bad))
    assert r.returncode == 2 and not (tmp_path / "b.json").exists()


def test_early_closed_pipe_preserves_failing_exit(tmp_path):
    """`tpudist-check | head -1` on a failing tree must still exit
    nonzero — the BrokenPipeError path reports the verdict already
    reached, not an unconditional 0."""
    haz = tmp_path / "haz.py"
    haz.write_text(_AXIS_PREAMBLE + "import jax\n" + "\n".join(
        f"def f{i}(x, rank):\n"
        f"    if rank == 0:\n"
        f"        x = jax.lax.psum(x, 'data')\n"
        f"    return x\n" for i in range(400)))
    script = (f"import sys; sys.argv=['c','--no-baseline',{str(haz)!r}]; "
              f"from tpudist.check import main; sys.exit(main())")
    head = subprocess.Popen(["head", "-c", "80"], stdin=subprocess.PIPE,
                            stdout=subprocess.DEVNULL)
    r = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                       stdout=head.stdin, stderr=subprocess.DEVNULL,
                       timeout=300)
    head.stdin.close()
    head.wait(timeout=30)
    assert r.returncode == 1, r.returncode


# -- whole-program analysis: cross-module fixture packages -------------------

def make_tree(tmp_path, files):
    """A multi-file fixture package under its own root (run_check walks
    it, so symbol-table resolution sees the whole mini-tree)."""
    root = tmp_path / "tree"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src).lstrip("\n"))
    return str(root)


def test_cross_module_trace_purity(tmp_path):
    """The hazard lives in helpers.py; the jit that reaches it lives in
    step.py. Intra-module analysis saw nothing; the call graph follows the
    import edge."""
    root = make_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/helpers.py": """
            import time


            def scale(x):
                return x * time.time()
            """,
        "pkg/step.py": """
            import jax
            from pkg.helpers import scale


            def step(x):
                return scale(x)


            train = jax.jit(step)
            """,
    })
    findings, _ = core.run_check(root)
    assert [(f.rule, f.path) for f in findings] \
        == [("TRACE01", "pkg/helpers.py")]


def test_jit_of_imported_function_seeds_it_traced(tmp_path):
    """``jax.jit(imported_fn)`` roots a function the importing module's
    own index cannot see — the cross-module SEED, not just cross-module
    edges."""
    root = make_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/impl.py": """
            import time


            def step(x):
                return x * time.time()
            """,
        "pkg/entry.py": """
            import jax
            from pkg.impl import step

            train = jax.jit(step)
            """,
    })
    findings, _ = core.run_check(root)
    assert [(f.rule, f.path) for f in findings] \
        == [("TRACE01", "pkg/impl.py")]


def test_cross_module_donated_step_flags_and_rebind_is_clean(tmp_path):
    """ISSUE 10 acceptance: the builder-in-one-module, consumer-in-another
    donation shape (the DONATE01 seed-bug class) flips the gate; the
    trainer's rebind-from-result pattern stays clean."""
    root = make_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/builder.py": """
            import jax


            def make_train_step(cfg):
                def step(s, b):
                    return s + b, s.mean()
                return jax.jit(step, donate_argnums=(0,))
            """,
        "pkg/consumer.py": """
            from pkg.builder import make_train_step


            def run(state, batch):
                step = make_train_step(None)
                out, m = step(state, batch)
                return state.mean()        # read after donation: garbage


            def run_safe(state, batches):
                step = make_train_step(None)
                for b in batches:
                    state, m = step(state, b)
                return state               # rebound from the result: fine
            """,
    })
    findings, _ = core.run_check(root)
    gated = core.gate(findings, baseline=set())
    assert [(f.rule, f.path, f.line) for f in gated] \
        == [("DONATE01", "pkg/consumer.py", 7)]


def test_cross_module_rank_guarded_collective_coll03(tmp_path):
    """ISSUE 10 acceptance: a rank-guarded call whose callee two hops away
    performs a collective (the PR 4 orbax-deadlock shape in its real
    cross-module form) flips the gate; the same call unguarded — and a
    guarded call to a collective-free callee — stay clean."""
    root = make_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/ckpt.py": """
            def flush_all(path):
                write(path)
                sync_all()


            def sync_all():
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices("ckpt")


            def host_only(path):
                write(path)
            """,
        "pkg/main.py": """
            from pkg.ckpt import flush_all, host_only


            def save(path, rank):
                if rank == 0:
                    flush_all(path)        # deadlock: peers never arrive


            def save_ok(path, rank):
                flush_all(path)            # symmetric: everyone arrives
                if rank == 0:
                    host_only(path)        # guarded host-local work: fine
            """,
    })
    findings, _ = core.run_check(root)
    gated = core.gate(findings, baseline=set())
    assert [(f.rule, f.path, f.line) for f in gated] \
        == [("COLL03", "pkg/main.py", 6)]
    assert "sync_global_devices" in gated[0].message


def test_coll03_respects_call_depth_bound(tmp_path):
    """A chain longer than max_call_depth is the documented conservative
    stop — no finding, no crash."""
    chain = "\n\n".join(
        f"def f{i}(x):\n    return f{i + 1}(x)" for i in range(6))
    root = make_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/deep.py": chain + """


def f6(x):
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("deep")
""",
        "pkg/main.py": """
            from pkg.deep import f0


            def save(x, rank):
                if rank == 0:
                    f0(x)
            """,
    })
    deep, _ = core.run_check(root, max_call_depth=2)
    assert [f.rule for f in deep if f.rule == "COLL03"] == []
    full, _ = core.run_check(root)      # default depth: chain resolves
    assert [f.rule for f in full if f.rule == "COLL03"] == ["COLL03"]


def test_coll01_return_in_loop_pairs_with_collective_after_loop(tmp_path):
    """Satellite: the documented false negative, closed. A rank-guarded
    `return` INSIDE a loop escapes the function, so it pairs with
    collectives after the loop; a `continue` only exits the loop and does
    NOT poison post-loop code."""
    findings = run_on(tmp_path, """
        import jax


        def f(loader, rank):
            for b in loader:
                if rank == 0:
                    return
            jax.lax.psum(1.0, "data")


        def g(loader, rank):
            for b in loader:
                if rank == 0:
                    continue
            jax.lax.psum(1.0, "data")
        """)
    assert [(f.rule, f.line) for f in findings
            if not f.suppressed] == [("COLL01", 10)]


# -- SHARD01/02/03: sharding/mesh consistency --------------------------------

def test_shard01_spec_axis_must_be_mesh_declared(tmp_path):
    """A P entry naming an axis no Mesh declares flags — including through
    a straight-line variable; a declared axis, a dynamic entry, and a
    mesh-free tree (nothing to check against) stay clean."""
    root = make_tree(tmp_path, {"m.py": """
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(devs(), ("data", "model"))
        AXIS = "modle"
        bad = P(None, AXIS)
        good = P("model", None)
        dyn = P(pick_axis())
        """})
    findings, _ = core.run_check(root)
    assert [(f.rule, f.line) for f in findings] == [("SHARD01", 5)]
    assert "modle" in findings[0].message
    meshless = make_tree(tmp_path / "b", {"m.py": """
        from jax.sharding import PartitionSpec as P

        spec = P("anything")
        """})
    findings, _ = core.run_check(meshless)
    assert rule_ids(findings) == []


def test_shard02_in_specs_arity(tmp_path):
    """in_specs that cannot match the wrapped function's signature flags;
    a matching tuple, a partial-bound callee, and *args stay clean. The
    callee resolves through the nested-def builder shape (the repo's
    make_*_step pattern)."""
    root = make_tree(tmp_path, {"m.py": """
        import jax
        from functools import partial
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(devs(), ("data",))


        def make_step():
            def step(state, images, labels):
                return state

            bad = shard_map(step, mesh=mesh,
                            in_specs=(P(), P("data")), out_specs=P())
            good = shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data"), P("data")),
                             out_specs=P())
            return bad, good


        def spmd(params, x, axis_name="data"):
            return x


        bound = shard_map(partial(spmd, None), mesh=mesh,
                          in_specs=(P("data"),), out_specs=P("data"))


        def variadic(*args):
            return args


        star = shard_map(variadic, mesh=mesh,
                         in_specs=(P(), P(), P(), P()), out_specs=P())
        """})
    findings, _ = core.run_check(root)
    assert [(f.rule, f.line) for f in findings] == [("SHARD02", 13)]
    assert "cannot match" in findings[0].message


def test_shard02_out_specs_arity(tmp_path):
    root = make_tree(tmp_path, {"m.py": """
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(devs(), ("data",))


        def step(state):
            return state, {}


        bad = shard_map(step, mesh=mesh, in_specs=(P(),),
                        out_specs=(P(), P(), P()))
        good = shard_map(step, mesh=mesh, in_specs=(P(),),
                         out_specs=(P(), P()))
        """})
    findings, _ = core.run_check(root)
    assert [(f.rule, f.line) for f in findings] == [("SHARD02", 11)]
    assert "2-tuple" in findings[0].message


def test_shard02_lexical_resolution_of_same_named_nested_steps(tmp_path):
    """Two builders each nest their own `step` (the real train.py shape:
    make_train_step and make_eval_step both do) — each shard_map site must
    resolve ITS step by lexical scoping, not give up on the ambiguous
    module-wide name."""
    root = make_tree(tmp_path, {"m.py": """
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(devs(), ("data",))


        def make_train_step():
            def step(state, images, labels, lr):
                return state, {}
            return shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data"), P("data"), P()),
                             out_specs=(P(), P()))


        def make_eval_step():
            def step(state, images, labels):
                return {}
            return shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data")),
                             out_specs=P())
        """})
    findings, _ = core.run_check(root)
    assert [(f.rule, f.line) for f in findings] == [("SHARD02", 18)]
    assert "make_eval_step.<locals>.step" in findings[0].message


_SHARD03_TP = """
    VIT_RULES = (("in_proj/kernel$", None),)
    RESNET_RULES = ()
    NO_TP_FAMILIES = ("resnet",)


    def rules_for(arch):
        if arch.startswith("vit"):
            return VIT_RULES
        return RESNET_RULES
    """


def test_shard03_unannotated_empty_rule_table(tmp_path):
    """A registered family resolving to an empty TP rule table with no
    NO_TP_FAMILIES annotation flags — including names registered through
    a literal loop and a cross-module _VARIANTS dict; annotated and ruled
    families stay clean. No 'model' mesh axis → rule stands down."""
    files = {
        "models/regnet.py": """
            _VARIANTS = {"regnet_x_400mf": 1, "regnet_y_400mf": 2}
            """,
        "models/__init__.py": """
            from models import regnet as _regnet_mod


            def register_model(name, ctor=None):
                pass


            register_model("plainnet9", object)    # unannotated: flags
            register_model("resnet18", object)     # NO_TP: clean
            for _n in ("vit_b_16", "vit_l_16"):    # ruled family: clean
                register_model(_n, object)
            for _n in _regnet_mod._VARIANTS:       # unannotated: flags x2
                register_model(_n, object)
            """,
        "parallel/tensor_parallel.py": _SHARD03_TP,
        "main.py": """
            from jax.sharding import Mesh

            mesh = Mesh(devs(), ("data", "model"))
            """,
    }
    root = make_tree(tmp_path, files)
    findings, _ = core.run_check(root)
    hits = [(f.rule, f.path) for f in findings]
    assert hits == [("SHARD03", "models/__init__.py")] * 3
    msgs = " ".join(f.message for f in findings)
    assert "plainnet9" in msgs and "regnet_x_400mf" in msgs \
        and "regnet_y_400mf" in msgs
    assert "resnet18" not in msgs and "vit_b_16" not in msgs
    # Same tree without a model-axis mesh: SHARD03 stands down.
    files["main.py"] = ('from jax.sharding import Mesh\n'
                        'mesh = Mesh(devs(), ("data",))\n')
    root2 = make_tree(tmp_path / "nomodel", files)
    findings, _ = core.run_check(root2)
    assert [f for f in findings if f.rule == "SHARD03"] == []


def test_shard04_rs_ag_pairing_consistency(tmp_path):
    """A psum_scatter paired with an all_gather over DIFFERENT literal
    axes — or the same axis but different tensor dims (absent kwarg = the
    documented default 0) — flags inside one outermost function (nested
    helper defs included: the step-builder shape). A consistent pair,
    unpaired calls, variable-resolved axes, and non-literal dims (the
    spec-driven builders) stay clean."""
    root = make_tree(tmp_path, {"m.py": """
        import jax
        from jax.sharding import Mesh

        mesh = Mesh(devs(), ("data", "model"))


        def bad_axis(p, g):
            full = jax.lax.all_gather(p, "model", axis=0, tiled=True)
            red = jax.lax.psum_scatter(g, "data", scatter_dimension=0,
                                       tiled=True)
            return full, red


        def bad_dim(p, g):
            full = jax.lax.all_gather(p, "data", axis=1, tiled=True)
            red = jax.lax.psum_scatter(g, "data", tiled=True)
            return full, red


        def good(p, g):
            def gather(x):
                return jax.lax.all_gather(x, "data", axis=0, tiled=True)

            red = jax.lax.psum_scatter(g, "data", scatter_dimension=0,
                                       tiled=True)
            return gather(p), red


        def var_axis(p, g, ax=0):
            full = jax.lax.all_gather(p, "data", axis=ax, tiled=True)
            red = jax.lax.psum_scatter(g, "data", scatter_dimension=ax,
                                       tiled=True)
            return full, red


        def unpaired(g):
            return jax.lax.psum_scatter(g, "data", scatter_dimension=1,
                                        tiled=True)
        """})
    findings, _ = core.run_check(root)
    hits = [(f.rule, f.line) for f in findings if f.rule == "SHARD04"]
    assert hits == [("SHARD04", 9), ("SHARD04", 16)], [
        (f.rule, f.line, f.message) for f in findings]
    msgs = {f.line: f.message for f in findings if f.rule == "SHARD04"}
    assert "re-tiles" in msgs[9]
    assert "transposed against the cut" in msgs[16]


def test_coll02_propagates_through_variables_and_constants(tmp_path):
    """Satellite of the literal-only limit: a typo'd axis forwarded
    through a local variable — or a cross-module constant — still flags;
    a correctly-forwarded declared axis stays clean."""
    root = make_tree(tmp_path, {
        "pkg/__init__.py": "",
        # NB: the typo'd constant must not be *_AXIS-named — axis-named
        # module constants DECLARE their value by the harvest convention.
        "pkg/names.py": 'DATA_AXIS = "data"\nREDUCE_OVER = "dta"\n',
        "pkg/m.py": """
            import jax
            from pkg.names import DATA_AXIS, REDUCE_OVER


            def good(x):
                ax = DATA_AXIS
                return jax.lax.pmean(x, axis_name=ax)


            def bad(x):
                ax = REDUCE_OVER
                return jax.lax.pmean(x, axis_name=ax)
            """,
    })
    findings, _ = core.run_check(root)
    assert [(f.rule, f.path, f.line) for f in findings] \
        == [("COLL02", "pkg/m.py", 12)]
    assert "dta" in findings[0].message


def test_recomp02_stands_down_for_array_wrapping_helper(tmp_path):
    """Satellite: a loop-varying scalar routed through a repo-local helper
    whose every return wraps in jnp.asarray is safe (the call graph makes
    the one-level crossing visible); the raw scalar still warns."""
    root = make_tree(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/h.py": """
            import jax.numpy as jnp


            def to_arr(x):
                return jnp.asarray(x, jnp.float32)
            """,
        "pkg/m.py": """
            import jax
            from pkg.h import to_arr

            step = jax.jit(lambda s, lr: s * lr)


            def fit(state, n):
                for i in range(n):
                    state = step(state, to_arr(0.1 * (1 - i / n)))
                return state


            def fit_bad(state, n):
                for i in range(n):
                    state = step(state, 0.1 * (1 - i / n))
                return state
            """,
    })
    findings, _ = core.run_check(root)
    assert [(f.rule, f.path, f.line) for f in findings] \
        == [("RECOMP02", "pkg/m.py", 15)]


# -- result cache + --diff ---------------------------------------------------

def test_cache_invalidation_on_content_change(tmp_path):
    """Warm run reuses everything; touching ONE file re-analyzes only that
    file (comment edits don't change the whole-program digest); a finding
    seeded into the changed file appears."""
    root = make_tree(tmp_path, {
        "a.py": "x = 1\n",
        "b.py": "DATA_AXIS = 'data'\ny = 2\n",
    })
    cdir = str(tmp_path / "cache")
    _, s1 = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert s1["cache"]["mode"] == "cold" and s1["cache"]["analyzed"] == 2
    _, s2 = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert s2["cache"] == {"mode": "warm", "reused": 2, "analyzed": 0}
    with open(os.path.join(root, "a.py"), "a") as f:
        f.write("# a comment only\n")
    _, s3 = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert s3["cache"] == {"mode": "partial", "reused": 1, "analyzed": 1}
    with open(os.path.join(root, "a.py"), "a") as f:
        f.write("import jax\n\n\ndef f(x, rank):\n"
                "    if rank == 0:\n"
                "        x = jax.lax.psum(x, 'data')\n    return x\n")
    f4, s4 = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert [f.rule for f in f4 if not f.suppressed] == ["COLL01"]
    # The hazard changed a.py's whole-program facts (new function), so the
    # digest flipped and everything re-analyzed — conservative, correct.
    assert s4["cache"]["mode"] in ("cold", "partial")
    f5, s5 = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert s5["cache"]["mode"] == "warm"
    assert [f.rule for f in f5 if not f.suppressed] == ["COLL01"]


def test_warm_cache_is_measurably_faster_than_cold():
    """ISSUE 10 acceptance: warm-cache full-tree runtime measurably below
    cold — asserted, not eyeballed. The warm path skips parse, callgraph,
    and every check; a 2x margin is far inside the real ~15x gap."""
    import shutil
    import time
    cdir = os.path.join(REPO, ".pytest_cache", "check-warm-test")
    shutil.rmtree(cdir, ignore_errors=True)
    t0 = time.monotonic()
    _, s_cold = core.run_check(REPO, use_cache=True, cache_dir=cdir)
    cold = time.monotonic() - t0
    t0 = time.monotonic()
    _, s_warm = core.run_check(REPO, use_cache=True, cache_dir=cdir)
    warm = time.monotonic() - t0
    shutil.rmtree(cdir, ignore_errors=True)
    assert s_cold["cache"]["mode"] == "cold"
    assert s_warm["cache"]["mode"] == "warm"
    assert warm < cold / 2, f"warm {warm:.3f}s not below cold {cold:.3f}s/2"


def test_corrupt_cache_degrades_to_cold(tmp_path):
    """Whole-file corruption AND a malformed entry inside a schema-valid
    file both mean 'cold run', never an internal-error exit."""
    from tpudist.analysis import cache as cache_mod
    root = make_tree(tmp_path, {"a.py": "x = 1\n"})
    cdir = str(tmp_path / "cache")
    core.run_check(root, use_cache=True, cache_dir=cdir)
    path = cache_mod.cache_file(root, cdir)
    with open(path, "w") as f:
        f.write("{not json")
    _, s = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert s["cache"]["mode"] == "cold"
    obj = cache_mod.load(root, cdir)
    obj["files"]["a.py"] = "junk"         # entry-level mangling
    with open(path, "w") as f:
        json.dump(obj, f)
    _, s = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert s["cache"]["mode"] == "cold"


def test_cache_invalidates_on_cross_module_constant_value_change(tmp_path):
    """A consumer file resolves its axis THROUGH a constant in another
    module; editing only the constant's VALUE must not replay the cached
    green verdict for the (unchanged) consumer file."""
    root = make_tree(tmp_path, {
        "consts.py": 'DATA_AXIS = "data"\nREDUCE_OVER = "data"\n',
        "use.py": """
            import jax
            from consts import REDUCE_OVER


            def f(x):
                return jax.lax.psum(x, REDUCE_OVER)
            """,
    })
    cdir = str(tmp_path / "cache")
    f1, _ = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert rule_ids(f1) == []
    (tmp_path / "tree" / "consts.py").write_text(
        'DATA_AXIS = "data"\nREDUCE_OVER = "dat"\n')
    f2, _ = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert [(f.rule, f.path) for f in f2] == [("COLL02", "use.py")]


def test_warm_cache_invalidates_on_docs_change(tmp_path):
    """TELEM03 reads docs/OBSERVABILITY.md — a docs-only edit (no .py
    change) must not hit the fully-warm short-circuit with stale
    verdicts."""
    root = make_tree(tmp_path, {
        "tpudist/telemetry.py": 'SCHEMA = {\n    "step": ("step",),\n}\n',
        "docs/OBSERVABILITY.md": "| step | trainer |\n",
    })
    cdir = str(tmp_path / "cache")
    f1, _ = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert rule_ids(f1) == []
    (tmp_path / "tree" / "docs" / "OBSERVABILITY.md").write_text(
        "| nothing here |\n")
    f2, s2 = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert s2["cache"]["mode"] != "warm"
    assert [f.rule for f in f2] == ["TELEM03"]


def test_warm_cache_is_keyed_by_call_depth(tmp_path):
    """A depth-limited run sees FEWER cross-module facts; its cache must
    not satisfy a later default-depth run's warm path (which would replay
    the weaker verdicts)."""
    root = make_tree(tmp_path, {
        "m.py": ("import jax\nfrom b import g1\n\n\ndef step(x):\n"
                 "    return g1(x)\n\n\ntrain = jax.jit(step)\n"),
        "b.py": "from c import g2\n\n\ndef g1(x):\n    return g2(x)\n",
        "c.py": "def g2(x):\n    print(x)\n    return x\n",
    })
    cdir = str(tmp_path / "cache")
    shallow, _ = core.run_check(root, use_cache=True, cache_dir=cdir,
                                max_call_depth=1)
    assert rule_ids(shallow) == []        # chain truncated: documented stop
    full, s = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert s["cache"]["mode"] != "warm"
    assert [(f.rule, f.path) for f in full] == [("TRACE01", "c.py")]


def test_cache_invalidates_on_callee_return_arity_change(tmp_path):
    """SHARD02's out_specs verdict in a.py depends on b.py's return
    shape — editing only b.py must not reuse a.py's cached green result."""
    root = make_tree(tmp_path, {
        "a.py": """
            from jax import shard_map
            from jax.sharding import Mesh, PartitionSpec as P
            from b import step

            mesh = Mesh(devs(), ("data",))
            wrapped = shard_map(step, mesh=mesh, in_specs=(P(),),
                                out_specs=(P(), P()))
            """,
        "b.py": "def step(state):\n    return state, {}\n",
    })
    cdir = str(tmp_path / "cache")
    f1, _ = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert rule_ids(f1) == []
    (tmp_path / "tree" / "b.py").write_text(
        "def step(state):\n    return state, {}, 0\n")
    f2, _ = core.run_check(root, use_cache=True, cache_dir=cdir)
    assert [(f.rule, f.path) for f in f2] == [("SHARD02", "a.py")]


def test_diff_mode_with_root_below_git_toplevel(tmp_path):
    """--root below the git toplevel: git reports 'sub/m.py' but findings
    say 'm.py' — --relative keeps them in agreement, so a changed-line
    hazard still gates."""
    top = tmp_path / "repo"
    sub = top / "sub"
    sub.mkdir(parents=True)
    (sub / "m.py").write_text("DATA_AXIS = 'data'\nx = 1\n")
    _git("init", "-q", cwd=str(top))
    _git("add", "-A", cwd=str(top))
    _git("commit", "-qm", "clean", cwd=str(top))
    with open(sub / "m.py", "a") as f:
        f.write("import jax\n\n\ndef f(x, rank):\n    if rank == 0:\n"
                "        x = jax.lax.psum(x, 'data')\n    return x\n")
    r = subprocess.run(
        [sys.executable, "-m", "tpudist.check", "--root", str(sub),
         "--no-baseline", "--no-cache", "--diff", "HEAD"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr


def _git(*args, cwd):
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    *args], cwd=cwd, check=True, capture_output=True)


def test_diff_mode_gates_only_changed_lines(tmp_path):
    """--diff semantics: a hazard on a changed line gates (exit 1); the
    SAME committed hazard with only unrelated lines changed does not
    (exit 0, reported off-diff); a hazard in a brand-new file gates."""
    root = make_tree(tmp_path, {
        "m.py": "DATA_AXIS = 'data'\nx = 1\n",
    })
    _git("init", "-q", cwd=root)
    _git("add", "-A", cwd=root)
    _git("commit", "-qm", "clean", cwd=root)

    hazard = ("import jax\n\n\ndef f(x, rank):\n    if rank == 0:\n"
              "        x = jax.lax.psum(x, 'data')\n    return x\n")

    def cli(*args):
        # cwd=REPO so `-m tpudist.check` resolves; the analyzed tree and
        # its git history are reached via --root / `git -C`.
        return subprocess.run(
            [sys.executable, "-m", "tpudist.check", "--root", root,
             "--no-baseline", "--no-cache", *args],
            cwd=REPO, capture_output=True, text=True, timeout=300)

    # 1. changed-line hit: the hazard appended to a tracked file gates.
    with open(os.path.join(root, "m.py"), "a") as f:
        f.write(hazard)
    r = cli("--diff", "HEAD", "--json")
    assert r.returncode == 1, r.stderr
    obj = json.loads(r.stdout)
    assert obj["counts"]["new"] == 1 and obj["diff"]["ref"] == "HEAD"
    # 2. unchanged-line miss: hazard committed, an unrelated edit on top —
    #    the finding exists but sits off-diff; the gate passes.
    _git("add", "-A", cwd=root)
    _git("commit", "-qm", "hazard accepted", cwd=root)
    with open(os.path.join(root, "m.py"), "a") as f:
        f.write("\nz = 3\n")
    r = cli("--diff", "HEAD", "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    obj = json.loads(r.stdout)
    assert obj["counts"]["new"] == 0 and len(obj["diff"]["off_diff"]) == 1
    # 3. new (untracked) file: every line is fair game.
    with open(os.path.join(root, "fresh.py"), "w") as f:
        f.write("DATA_AXIS = 'data'\n" + hazard)
    r = cli("--diff", "HEAD")
    assert r.returncode == 1, r.stdout + r.stderr
    # 4. a ref git can't resolve is a usage error, never a green gate.
    r = cli("--diff", "NOT_A_REF")
    assert r.returncode == 2


def test_write_baseline_prunes_stale_entries(tmp_path):
    """Satellite: --write-baseline drops fingerprints that no longer exist
    on the tree and reports the pruned count; entries for paths OUTSIDE an
    explicit-paths run are kept."""
    src_hazard = _AXIS_PREAMBLE + textwrap.dedent("""
        import jax


        def f(x, rank):
            if rank == 0:
                x = jax.lax.psum(x, "data")
            return x
        """)
    p = tmp_path / "h.py"
    p.write_text(src_hazard)
    base = tmp_path / "base.json"
    findings, stats = core.run_check(REPO, paths=[str(p)])
    data, pruned = core.write_baseline(
        str(base), findings, analyzed_paths=set(stats["relpaths"]))
    assert len(data["entries"]) == 1 and pruned == 0
    # Fix the hazard: rewriting prunes the stale fingerprint and says so.
    p.write_text(_AXIS_PREAMBLE + "x = 1\n")
    findings, stats = core.run_check(REPO, paths=[str(p)])
    data, pruned = core.write_baseline(
        str(base), findings, analyzed_paths=set(stats["relpaths"]))
    assert data["entries"] == [] and pruned == 1
    # Entries for paths outside the analyzed set survive a subset run.
    foreign = {"rule": "COLL01", "path": "elsewhere.py", "line": 1,
               "fingerprint": "f" * 16, "message": "kept"}
    base.write_text(json.dumps({"version": 1, "entries": [foreign]}))
    data, pruned = core.write_baseline(
        str(base), findings, analyzed_paths=set(stats["relpaths"]))
    assert pruned == 0 and data["entries"] == [foreign]


# -- ELASTIC01: the host-side reshard contract (ISSUE 13) --------------------

def test_elastic01_direct_jax_import_fires(tmp_path):
    """Any jax import in elastic/reshard.py — module-level OR
    function-local (the lazy form still breaks the jax-free supervisor
    image) — fires; numpy and stdlib stay legal."""
    root = make_tree(tmp_path, {
        "elastic/__init__.py": "",
        "elastic/reshard.py": """
            import jax


            def cut_state(tree, world):
                return tree
            """,
    })
    findings, _ = core.run_check(root)
    assert "ELASTIC01" in rule_ids(findings)

    root2 = make_tree(tmp_path / "b", {
        "elastic/__init__.py": "",
        "elastic/reshard.py": """
            def merge_state(shards, layout):
                from jax.sharding import PartitionSpec
                return shards[0]
            """,
    })
    findings, _ = core.run_check(root2)
    assert "ELASTIC01" in rule_ids(findings)


def test_elastic01_indirect_via_jax_importing_module_fires(tmp_path):
    """The tempting refactor: import a helper from a module that imports
    jax at module level (the parallel/ twin of zero_full_axis) — the
    indirect break the symbol table resolves."""
    root = make_tree(tmp_path, {
        "elastic/__init__.py": "",
        "elastic/reshard.py": """
            from parallel.helper import zero_axis


            def cut_state(tree, world):
                return zero_axis(tree, world)
            """,
        "parallel/__init__.py": "",
        "parallel/helper.py": """
            import jax


            def zero_axis(tree, world):
                return 0
            """,
    })
    findings, _ = core.run_check(root)
    assert "ELASTIC01" in rule_ids(findings)


def test_elastic01_negative_numpy_only_and_scope(tmp_path):
    """Negative fixtures: a numpy-only reshard.py (even importing a
    numpy-only sibling) is clean, and jax imports in OTHER files never
    trip this rule (it pins one module's contract)."""
    root = make_tree(tmp_path, {
        "elastic/__init__.py": "",
        "elastic/reshard.py": """
            import re

            import numpy as np

            from elastic.membership import reform_world


            def cut_state(tree, world):
                return [np.asarray(x) for x in tree], reform_world
            """,
        "elastic/membership.py": """
            def reform_world(w):
                return w - 1
            """,
        "parallel/plane.py": """
            import jax


            def host_rules(rules):
                return tuple(rules)
            """,
    })
    findings, _ = core.run_check(root)
    assert "ELASTIC01" not in rule_ids(findings), findings


def test_elastic01_repo_reshard_is_clean():
    """The committed elastic/reshard.py satisfies its own contract (the
    rule runs in the repo-wide gate; this pins the target file names)."""
    findings, _ = core.run_check(
        REPO, paths=[os.path.join(REPO, "tpudist", "elastic", "reshard.py")])
    assert "ELASTIC01" not in rule_ids(findings)


# -- the tier-1 gate: the committed tree is clean ----------------------------

def test_repo_tree_is_clean():
    """THE gate: zero unsuppressed gating findings on the committed tree
    against the committed baseline (which is expected to be EMPTY — debt
    goes through pragmas-with-reasons, not the baseline)."""
    findings, stats = core.run_check(REPO)
    baseline = core.load_baseline(
        os.path.join(REPO, "tools", "check_baseline.json"))
    new = core.gate(findings, baseline)
    assert new == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule}: {f.message}" for f in new)
    # Suppressions on the committed tree all carry reasons.
    assert not [f for f in findings if f.rule == "PRAGMA01"]
    assert stats["files"] > 80      # the walk really covered the tree


def test_analyzer_imports_no_jax():
    """Zero-dependency invariant: importing and running the checker must
    not drag jax in (the launcher-image use case)."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; from tpudist.analysis import core; "
         "core.run_check(sys.argv[1], paths=[]); "
         "assert 'jax' not in sys.modules, 'analyzer imported jax'",
         REPO],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_seeded_hazards_flip_the_gate(tmp_path):
    """Acceptance criterion, demonstrated per rule family: the clean tree
    exits 0; introducing any ONE of the six hazard classes exits nonzero."""
    seeds = {
        "TRACE01": """
            import time, jax
            def step(x):
                return x * time.time()
            f = jax.jit(step)
            """,
        "COLL01": """
            import jax
            def step(x, rank):
                if rank == 0:
                    x = jax.lax.psum(x, "data")
                return x
            """,
        "DONATE01": """
            import jax
            def run(state, b):
                step = jax.jit(lambda s, b: s + b, donate_argnums=(0,))
                out = step(state, b)
                return state
            """,
        "PALLAS01": """
            from tpudist.ops.pallas import flash_attention
            """,
        "TELEM01": """
            def report(tel):
                tel.emit("not_a_real_event", x=1)
            """,
        "RECOMP01": """
            import jax
            def sweep(xs):
                for x in xs:
                    jax.jit(lambda v: v)(x)
            """,
    }
    for rule, src in seeds.items():
        findings = run_on(tmp_path, src, name=f"seed_{rule.lower()}.py")
        gated = core.gate(findings, baseline=set())
        assert any(f.rule == rule for f in gated), \
            f"{rule} seed did not gate: {findings}"
    # ISSUE 10: the matrix gains CROSS-MODULE hazard classes — the guard
    # and the collective (COLL03), and the donation and the read
    # (DONATE01), each split across two files — plus the SHARD family.
    xmod_seeds = {
        "COLL03": {
            "pkg/__init__.py": "",
            "pkg/a.py": """
                def sync():
                    from jax.experimental import multihost_utils
                    multihost_utils.sync_global_devices("x")
                """,
            "pkg/b.py": """
                from pkg.a import sync


                def save(rank):
                    if rank == 0:
                        sync()
                """,
        },
        "DONATE01": {
            "pkg/__init__.py": "",
            "pkg/a.py": """
                import jax


                def make_step():
                    return jax.jit(lambda s: s, donate_argnums=(0,))
                """,
            "pkg/b.py": """
                from pkg.a import make_step


                def run(state):
                    step = make_step()
                    out = step(state)
                    return state
                """,
        },
        "SHARD01": {
            "m.py": """
                from jax.sharding import Mesh, PartitionSpec as P

                mesh = Mesh(devs(), ("data",))
                spec = P("dta")
                """,
        },
        "SHARD03": {
            "models/__init__.py": """
                def register_model(name, ctor=None):
                    pass


                register_model("plainnet9", object)
                """,
            "parallel/tensor_parallel.py": _SHARD03_TP,
            "main.py": """
                from jax.sharding import Mesh

                mesh = Mesh(devs(), ("data", "model"))
                """,
        },
        # ISSUE 12: a mis-ruled table — a spec axis outside the plane's
        # AXIS_BINDING range — flips the gate (the acceptance-matrix
        # proof that SHARD05 fires on a seeded bad rule table).
        "SHARD05": {
            "parallel/plane.py": """
                AXIS_BINDING = {
                    "dp": "data",
                    "tp": "model",
                }
                """,
            "parallel/tensor_parallel.py": """
                from jax.sharding import PartitionSpec as P

                RESNET_RULES = (("conv/kernel$", P(None, "seq")),)
                """,
            "main.py": """
                from jax.sharding import Mesh

                mesh = Mesh(devs(), ("data", "model", "seq"))
                """,
        },
        # ISSUE 13: jax reaching the host-side cut/merge surface flips
        # the gate (the ELASTIC01 acceptance-matrix proof).
        "ELASTIC01": {
            "elastic/__init__.py": "",
            "elastic/reshard.py": """
                import jax


                def cut_state(tree, world):
                    return tree
                """,
        },
    }
    for rule, files in xmod_seeds.items():
        root = make_tree(tmp_path / f"xmod_{rule.lower()}", files)
        findings, _ = core.run_check(root)
        gated = core.gate(findings, baseline=set())
        assert any(f.rule == rule for f in gated), \
            f"{rule} cross-module seed did not gate: {findings}"
    # ISSUE 14: the serving-loop recompile hazard — a jitted step keyed on
    # len(batch) inside the request pump — flips the strict gate
    # (RECOMP02 is a warning-severity heuristic, so the acceptance proof
    # runs the gate the pre-commit --strict surface runs).
    serve_seed = """
        import jax

        step = jax.jit(lambda imgs, n: imgs)


        def serve(queue, imgs):
            while queue:
                batch = queue.pop()
                step(imgs, len(batch))
        """
    findings = run_on(tmp_path, serve_seed, name="seed_recomp_serve.py")
    gated = core.gate(findings, baseline=set(), strict=True)
    assert any(f.rule == "RECOMP02" for f in gated), \
        f"RECOMP02 serve seed did not gate under --strict: {findings}"


def test_check_smoke_script(tmp_path):
    """Satellite: tools/check_smoke.sh chains clean-tree → seeded hazard →
    baseline round trip → pragma → exit-code contract."""
    env = dict(os.environ)
    env["TPUDIST_CHECK_SMOKE_DIR"] = str(tmp_path)
    r = subprocess.run(["bash", os.path.join(REPO, "tools",
                                             "check_smoke.sh")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "CHECK_SMOKE_OK"


# -- SHARD05: rule-table / plane / shard_map-pallas consistency (ISSUE 12) ---

_PLANE_SRC = """
    AXIS_BINDING = {
        "dp": "data",
        "tp": "model",
        "zero": "data",
    }
    """


def test_shard05_rule_table_axis_must_be_plane_bound(tmp_path):
    """A *_RULES table naming a spec axis outside plane.AXIS_BINDING's
    range flags — even when SOME mesh declares that axis (the SHARD01
    blind spot: 'seq' is mesh-declared by the SP meshes but is not a
    TP-plane axis); plane-bound axes stay clean."""
    files = {
        "parallel/plane.py": _PLANE_SRC,
        "parallel/tensor_parallel.py": """
            from jax.sharding import PartitionSpec as P

            GOOD_RULES = (("a/kernel$", P(None, "model")),)
            BAD_RULES = (("b/kernel$", P(None, "seq")),)
            """,
        "main.py": """
            from jax.sharding import Mesh

            mesh = Mesh(devs(), ("data", "model", "seq"))
            """,
    }
    root = make_tree(tmp_path, files)
    findings, _ = core.run_check(root)
    hits = [(f.rule, f.path) for f in findings if f.rule == "SHARD05"]
    assert hits == [("SHARD05", "parallel/tensor_parallel.py")], findings
    msg = [f for f in findings if f.rule == "SHARD05"][0].message
    assert "BAD_RULES" in msg and "'seq'" in msg
    # Without a plane module the check stands down (conservative stop).
    del files["parallel/plane.py"]
    root2 = make_tree(tmp_path / "noplane", files)
    findings, _ = core.run_check(root2)
    assert [f for f in findings if f.rule == "SHARD05"] == []


def test_shard05_binding_must_be_mesh_declared(tmp_path):
    """The other end of end-to-end: a plane binding naming a mesh axis no
    Mesh declares flags at the binding site."""
    root = make_tree(tmp_path, {
        "parallel/plane.py": """
            AXIS_BINDING = {
                "dp": "data",
                "tp": "modle",
            }
            """,
        "main.py": """
            from jax.sharding import Mesh

            mesh = Mesh(devs(), ("data", "model"))
            """,
    })
    findings, _ = core.run_check(root)
    hits = [(f.rule, f.path) for f in findings if f.rule == "SHARD05"]
    assert hits == [("SHARD05", "parallel/plane.py")], findings
    assert "'modle'" in [f for f in findings
                         if f.rule == "SHARD05"][0].message


def test_shard05_pallas_shard_map_out_spec_consistency(tmp_path):
    """A shard_map wrapping a (transitively) pallas_call-performing kernel
    whose out_specs shard an axis no in_spec shards flags — a shard-local
    kernel cannot manufacture sharding; a consistent wrapper and a
    non-pallas callee stay clean."""
    root = make_tree(tmp_path, {
        "kern.py": """
            from jax.experimental import pallas as pl


            def kernel_fn(x_ref, o_ref):
                o_ref[...] = x_ref[...]


            def kernel(x):
                return pl.pallas_call(kernel_fn, out_shape=x)(x)
            """,
        "wrap.py": """
            import jax
            from jax.sharding import Mesh, PartitionSpec as P
            from kern import kernel

            mesh = Mesh(devs(), ("data", "model"))

            bad = jax.shard_map(kernel, mesh=mesh,
                                in_specs=(P("data", None),),
                                out_specs=P("data", "model"))
            good = jax.shard_map(kernel, mesh=mesh,
                                 in_specs=(P("data", "model"),),
                                 out_specs=P("data", "model"))


            def not_pallas(x):
                return x

            plain = jax.shard_map(not_pallas, mesh=mesh,
                                  in_specs=(P("data", None),),
                                  out_specs=P("data", "model"))
            """,
    })
    findings, _ = core.run_check(root)
    hits = [(f.rule, f.path, f.line) for f in findings
            if f.rule == "SHARD05"]
    assert hits == [("SHARD05", "wrap.py", 7)], findings
    msg = [f for f in findings if f.rule == "SHARD05"][0].message
    assert "model" in msg and "manufacture" in msg


def test_shard05_active_on_real_tree_and_clean():
    """On the committed plane + rule-table + kernel-wrapper files the rule
    is ACTIVE (the plane binding harvests — not a conservative
    stand-down) and finds nothing."""
    paths = [os.path.join(REPO, "tpudist", "parallel", "plane.py"),
             os.path.join(REPO, "tpudist", "parallel",
                          "tensor_parallel.py"),
             os.path.join(REPO, "tpudist", "ops", "pallas",
                          "flash_attention.py")]
    findings, _ = core.run_check(REPO, paths=paths)
    assert [f for f in findings if f.rule == "SHARD05"] == []
    # Harvest really resolved: the binding covers every axis the committed
    # conv/vit rule tables cut (a degenerate empty harvest would make the
    # clean run above vacuous).
    from tpudist.analysis import rules_sharding
    sources, _ = core.read_targets(REPO, paths, False)
    mods, _ = core.parse_sources(sources)
    ctx = core.build_context(REPO, mods, None)
    h = rules_sharding._harvest_plane(ctx)
    assert h.get("binding", {}).get("tp") == "model"
    assert set(h["binding"].values()) >= {"data", "model"}
