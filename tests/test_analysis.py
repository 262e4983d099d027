"""Static-analysis subsystem tests (ISSUE 13).

Each rule is exercised on small fixture modules (positive AND negative
cases), the suppression grammar is proven to require reasons, the HLO
rule helpers run on synthetic text plus one real fused/reference engine
pair, and — the gate — the whole repo runs CLEAN: zero unsuppressed
findings from both AST passes over ``langstream_tpu/``."""

import os
import textwrap

import pytest

from langstream_tpu.analysis.jit_hazards import run_jit_pass
from langstream_tpu.analysis.lock_discipline import run_lock_pass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "langstream_tpu")


def _write(tmp_path, source):
    path = tmp_path / "fixture.py"
    path.write_text(textwrap.dedent(source))
    return str(path)


def _rules(findings, suppressed=False):
    return sorted(
        f.rule for f in findings if f.suppressed == suppressed
    )


# ---------------------------------------------------------------------- #
# lock-discipline pass
# ---------------------------------------------------------------------- #
def test_guarded_by_read_and_write_violations(tmp_path):
    path = _write(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._items = []  # guarded-by: _lock
                self._lock = threading.Lock()

            def ok(self):
                with self._lock:
                    self._items.append(1)
                    return len(self._items)

            def bad_write(self):
                self._items.append(2)

            def bad_read(self):
                return len(self._items)
    """)
    findings = run_lock_pass([path])
    assert _rules(findings) == [
        "guarded-by-violation", "guarded-by-violation",
    ]
    kinds = {f.message.split(" ", 1)[0] for f in findings}
    assert kinds == {"write", "read"}


def test_guarded_by_writes_only_mode_and_requires_lock(tmp_path):
    path = _write(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._mode = "a"  # guarded-by: _lock (writes)
                self._n = 0  # guarded-by: _lock
                self._lock = threading.Lock()

            def free_read(self):
                return self._mode  # fine: writes-only annotation

            def bad_write(self):
                self._mode = "b"

            # requires-lock: _lock
            def helper(self):
                self._n += 1  # fine: caller holds the lock
    """)
    findings = run_lock_pass([path])
    assert _rules(findings) == ["guarded-by-violation"]
    assert "bad_write" in findings[0].message


def test_owned_by_violation_and_owner_reachability(tmp_path):
    path = _write(tmp_path, """
        import threading

        class Eng:
            def __init__(self):
                self.log = []  # owned-by: _loop
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                self._emit()

            def _emit(self):
                self.log.append(1)  # fine: reachable from the owner

            def external_mutation(self):
                self.log.append(2)

            def external_read(self):
                return list(self.log)  # reads are snapshots — allowed
    """)
    findings = run_lock_pass([path])
    assert _rules(findings) == ["owned-by-violation"]
    assert "external_mutation" in findings[0].message


def test_cross_thread_mutation_detection(tmp_path):
    """The PR-10 build_heartbeat failure class: an unannotated dict
    mutated both from the spawned thread and from callers."""
    path = _write(tmp_path, """
        import threading

        class Eng:
            def __init__(self):
                self.seen = {}
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                self.seen["k"] = 1

            def reset(self):
                self.seen.clear()
    """)
    findings = run_lock_pass([path])
    assert _rules(findings) == ["cross-thread-mutation"]
    assert "seen" in findings[0].message


def test_cross_thread_mutation_quiet_when_annotated(tmp_path):
    path = _write(tmp_path, """
        import threading

        class Eng:
            def __init__(self):
                self.seen = {}  # owned-by: _loop
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                self.seen["k"] = 1

            # lint: allow(owned-by-violation) -- idle-only by contract
            def reset(self):
                self.seen.clear()
    """)
    assert _rules(run_lock_pass([path])) == []


def test_annotation_typo_guards(tmp_path):
    """A typo'd lock/owner reports ONLY the typo — accesses against a
    misspelled contract would be noise on top of the actionable
    finding (writes to both attrs here must add nothing)."""
    path = _write(tmp_path, """
        class Box:
            def __init__(self):
                self._a = []  # guarded-by: _lokc
                self._b = []  # owned-by: _lop

            def touch(self):
                self._a.append(1)
                self._b.append(2)
                return self._a, self._b
    """)
    assert _rules(run_lock_pass([path])) == ["unknown-lock", "unknown-owner"]


def test_unanchored_annotation_is_a_finding(tmp_path):
    """An annotation that attaches to no self-attribute assignment
    declares a contract that checks nothing — same philosophy as the
    unknown-lock typo guard."""
    path = _write(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                # guarded-by: _lock
                print("not an assignment")
    """)
    assert _rules(run_lock_pass([path])) == ["unanchored-annotation"]


def test_suppression_requires_reason(tmp_path):
    path = _write(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._items = []  # guarded-by: _lock
                self._lock = threading.Lock()

            def bad(self):
                # lint: allow(guarded-by-violation)
                self._items.append(1)
    """)
    findings = run_lock_pass([path])
    assert _rules(findings, suppressed=True) == ["guarded-by-violation"]
    assert _rules(findings) == ["suppression-missing-reason"]


def test_suppression_with_reason_and_def_level_coverage(tmp_path):
    path = _write(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._items = []  # guarded-by: _lock
                self._lock = threading.Lock()

            # lint: allow(guarded-by-violation) -- init-only helper,
            #   runs before the object is published to other threads
            def prime(self):
                self._items.append(0)
                self._items.append(1)
    """)
    findings = run_lock_pass([path])
    assert _rules(findings) == []
    suppressed = [f for f in findings if f.suppressed]
    assert len(suppressed) == 2
    assert all("init-only helper" in f.reason for f in suppressed)


# ---------------------------------------------------------------------- #
# jit-hazard pass
# ---------------------------------------------------------------------- #
def test_tracer_host_sync_detection(tmp_path):
    path = _write(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def step(x, scale: float):
            value = float(x)          # tainted: x is a tracer
            host = np.asarray(x * 2)  # tainted derivation
            peak = x.max().item()     # .item() always flags
            knob = float(scale)       # fine: scalar-annotated param
            return value, host, peak, knob
    """)
    findings = run_jit_pass([path])
    assert _rules(findings) == ["tracer-host-sync"] * 3


def test_tracer_branch_detection_and_static_escapes(tmp_path):
    path = _write(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, tables):
            if x.sum() > 0:          # flagged: value branch
                x = x + 1
            if tables is None:       # fine: identity test is static
                x = x * 2
            if x.shape[0] > 4:       # fine: shapes are static
                x = x[:4]
            while len(x):            # fine: len() is static
                break
            return jnp.where(x > 0, x, 0)  # fine: device-side select
    """)
    findings = run_jit_pass([path])
    assert _rules(findings) == ["tracer-branch"]
    assert findings[0].line == 7


def test_scalar_forward_reference_matches_whole_words(tmp_path):
    """`x: "Interval"` must NOT read as int (substring trap); a real
    `"Optional[int]"` forward reference is static."""
    path = _write(tmp_path, """
        import jax

        @jax.jit
        def step(x: "Interval", k: "Optional[int]"):
            value = float(x)   # x is a tracer despite the 'int' substring
            if k:              # fine: genuine scalar forward reference
                value = value + k
            return value
    """)
    assert _rules(run_jit_pass([path])) == ["tracer-host-sync"]


def test_static_argnums_untaints_parameters(tmp_path):
    path = _write(tmp_path, """
        import functools

        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def step(x, mode):
            if mode:                 # fine: static arg
                return x + 1
            return x
    """)
    assert _rules(run_jit_pass([path])) == []


def test_jit_reachability_through_helpers(tmp_path):
    """A hazard in a helper only flags when a jit root reaches it."""
    hazardous = """
        import jax

        def helper(x):
            return float(x)

        def unreached(x):
            return float(x)

        @jax.jit
        def step(x):
            return helper(x)
    """
    findings = run_jit_pass([_write(tmp_path, hazardous)])
    assert _rules(findings) == ["tracer-host-sync"]
    assert "helper" in findings[0].message


def test_device_context_annotation_roots_analysis(tmp_path):
    path = _write(tmp_path, """
        # jit: device-context — jitted by callers in another module
        def decode_step(params, x):
            return float(x)
    """)
    findings = run_jit_pass([path])
    assert _rules(findings) == ["tracer-host-sync"]


def test_closure_mutable_config_detection(tmp_path):
    path = _write(tmp_path, """
        import jax

        def build(n):
            table = {"k": n}
            sizes = [n]

            @jax.jit
            def run(x):
                return x * table["k"] + sizes[0]

            @jax.jit
            def clean(x, table):
                return x * 2  # parameter shadows the outer name

            return run, clean
    """)
    findings = run_jit_pass([path])
    assert _rules(findings) == ["closure-mutable-config"] * 2
    assert all("run" in f.message for f in findings)


# ---------------------------------------------------------------------- #
# HLO rule helpers: synthetic text (no engine, no compile)
# ---------------------------------------------------------------------- #
def test_full_pool_allgather_lines_on_synthetic_hlo():
    from langstream_tpu.analysis.hlo_lint import (
        PoolDims,
        full_pool_allgather_lines,
    )

    dims = PoolDims(64, 8, 4, 16)
    bad = (
        "  %ag = f32[2,64,8,4,16]{4,3,2,1,0} all-gather(f32[2,64,8,2,16] "
        "%p), replica_groups={{0,1}}, dimensions={3}"
    )
    benign = (
        "  %ag2 = f32[4,128]{1,0} all-gather(f32[4,64] %act), "
        "replica_groups={{0,1}}, dimensions={1}"
    )
    text = "\n".join(["HloModule jit_run", bad, benign])
    lines = full_pool_allgather_lines(text, dims)
    assert lines == [bad]
    assert full_pool_allgather_lines(benign, dims) == []


def test_pool_gather_lines_on_synthetic_stablehlo():
    from langstream_tpu.analysis.hlo_lint import PoolDims, pool_gather_lines

    dims = PoolDims(65, 8, 4, 16)
    bad = (
        '  %g = "stablehlo.gather"(%pool, %idx) : '
        "(tensor<65x8x4x16xf32>, tensor<4x8x1xi32>) -> tensor<...>"
    )
    benign = '  %e = "stablehlo.gather"(%emb, %tok) : (tensor<256x64xf32>, ...)'
    assert pool_gather_lines("\n".join([bad, benign]), dims) == [bad]
    int8 = PoolDims(65, 8, 4, 16, dtype="i8")
    assert pool_gather_lines(bad, int8) == []  # dtype-exact match


def test_collective_census_and_donation_helpers():
    from langstream_tpu.analysis.hlo_lint import (
        collective_census,
        donation_alias_present,
    )

    text = "\n".join([
        "HloModule jit_run, input_output_alias={ {0}: (1, {}, may-alias) }",
        "  %a = f32[2] all-reduce(f32[2] %x), replica_groups={}",
        "  %b = f32[2] all-reduce(f32[2] %y), replica_groups={}",
        "  %c = f32[2,4] all-gather(f32[2,2] %z), dimensions={1}",
        "  %d = f32[2] collective-permute(f32[2] %w)",
        "  // comment mentioning all-to-all is not an op line",
    ])
    assert collective_census(text) == {
        "all-reduce": 2, "all-gather": 1, "collective-permute": 1,
    }
    assert donation_alias_present(text)
    assert not donation_alias_present("HloModule jit_run\n %a = f32[] foo")
    # an EMPTY alias map is a dropped donation, not a pass
    assert not donation_alias_present(
        "HloModule jit_run, input_output_alias={ }"
    )


# ---------------------------------------------------------------------- #
# HLO rules on a real engine pair (lowering only + ONE tiny compile)
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def engine_pair():
    from langstream_tpu.analysis.hlo_lint import build_engine

    fused = build_engine(
        dict(kv_layout="paged", kv_block_size=8, paged_kernel="fused")
    )
    reference = build_engine(
        dict(kv_layout="paged", kv_block_size=8, paged_kernel="reference")
    )
    yield fused, reference
    fused.retire()
    reference.retire()


def test_fused_dispatches_pass_pool_gather_rule(engine_pair):
    from langstream_tpu.analysis.hlo_lint import (
        lowered_text,
        pool_dims,
        pool_gather_lines,
    )

    fused, _ = engine_pair
    dims = pool_dims(fused)
    for fn in (
        fused._get_decode(1),
        fused._get_prefill(16),
        fused._get_prefill_offset(16),
    ):
        assert pool_gather_lines(lowered_text(fused, fn), dims) == []


def test_reference_decode_is_the_golden_positive(engine_pair):
    """The reference leg's gather/scatter copy IS the pattern the rule
    hunts — k and v pool gathers per layer scan."""
    from langstream_tpu.analysis.hlo_lint import (
        lowered_text,
        pool_dims,
        pool_gather_lines,
    )

    _, reference = engine_pair
    dims = pool_dims(reference)
    lines = pool_gather_lines(
        lowered_text(reference, reference._get_decode(1)), dims
    )
    assert len(lines) >= 2


def test_check_engine_runs_rule_library_clean(engine_pair):
    """check_engine on the fused tp=1 engine: every applicable rule
    (pool gather on lowered text; donation + census on ONE compiled
    dispatch) passes — the per-config arm of `langstream-tpu check`."""
    from langstream_tpu.analysis import hlo_lint

    fused, _ = engine_pair
    findings, census = hlo_lint.check_engine(
        fused,
        dispatches={"decode[1]": fused._get_decode(1)},
        config_name="paged-fused-tp1",
    )
    assert findings == []
    assert census == {"paged-fused-tp1:decode[1]": {}}  # tp=1: no collectives


def test_named_dispatches_cover_the_serving_surface(engine_pair):
    from langstream_tpu.analysis.hlo_lint import named_dispatches

    fused, _ = engine_pair
    names = set(named_dispatches(fused))
    assert {"decode[1]", "prefill[16]", "prefill_offset[16]",
            "block_copy"} <= names


# ---------------------------------------------------------------------- #
# retrace-count budget (analysis/retrace.py)
# ---------------------------------------------------------------------- #
def test_retrace_budget_negative_and_positive(engine_pair, monkeypatch):
    """Negative: a healthy engine's builders are memo-stable (zero
    findings). Positive: a builder whose memo is broken — the closure
    is rebuilt per call, so the same dispatch would be lowered more
    than once under different static closures — is flagged both by the
    direct probe and by the _variant_jobs stability sweep."""
    import functools

    from langstream_tpu.analysis import retrace

    fused, _ = engine_pair
    assert retrace.check_engine(fused, config_name="fused") == []

    class BrokenMemo:
        """Proxy whose _get_decode forgets its memo (fresh closure per
        call) — the exact bug class the budget exists to catch."""

        def __init__(self, engine):
            self._engine = engine

        def __getattr__(self, name):
            return getattr(self._engine, name)

        def _get_decode(self, steps):
            return functools.partial(self._engine._get_decode(steps))

        def _variant_jobs(self):
            return self._engine._variant_jobs()

    findings = retrace.check_engine(BrokenMemo(fused), config_name="broken")
    assert findings
    assert all(f.rule == "retrace-budget" for f in findings)
    assert any("_get_decode" in f.path for f in findings)

    # _variant_jobs-level instability (a memo the probe list does not
    # name): clearing the block-copy memo before each call makes the
    # job list resolve to a different fn object per sweep
    original = fused._get_block_copy

    def amnesiac():
        fused._block_copy_fn = None
        return original()

    monkeypatch.setattr(fused, "_get_block_copy", amnesiac)
    findings = retrace.check_engine(fused, config_name="amnesiac")
    monkeypatch.undo()
    fused._block_copy_fn = None  # drop the poisoned memo for later tests
    assert any("job[" in f.path or "_get_block_copy" in f.path
               for f in findings)


def test_retrace_pass_repo_clean():
    """The repo gate: every builder across the retrace matrix (dense +
    paged/fused/mixed/spec — all builder families) holds the one-
    lowering-per-static-key budget."""
    from langstream_tpu.analysis.retrace import run_retrace_pass

    assert run_retrace_pass() == []


# ---------------------------------------------------------------------- #
# the true-positive fix: snapshot-tolerant cross-thread reads
# ---------------------------------------------------------------------- #
class _FlakyDict(dict):
    """items() raises like a dict resized mid-iteration, N times."""

    def __init__(self, *args, fails=2, **kwargs):
        super().__init__(*args, **kwargs)
        self.fails = fails

    def items(self):
        if self.fails > 0:
            self.fails -= 1
            raise RuntimeError("dictionary changed size during iteration")
        return super().items()


class _FlakyIterable:
    def __init__(self, values, fails=2):
        self.values = values
        self.fails = fails

    def __iter__(self):
        if self.fails > 0:
            self.fails -= 1
            raise RuntimeError("Set changed size during iteration")
        return iter(self.values)


def test_stable_helpers_retry_past_resizes():
    from langstream_tpu.utils.threadsafe import stable_items, stable_list

    assert stable_items(_FlakyDict({"a": 1}, fails=3)) == [("a", 1)]
    assert stable_list(_FlakyIterable([1, 2], fails=3)) == [1, 2]
    # persistently hot: empty snapshot, never an exception
    assert stable_items(_FlakyDict({"a": 1}, fails=99)) == []
    assert stable_list(_FlakyIterable([1], fails=99)) == []


def test_engines_snapshot_survives_concurrent_stats_mutation(monkeypatch):
    """Regression for the lock-pass finding on DecodeEngine.stats: a
    /metrics scrape must survive the engine thread inserting a new
    wasted-tokens reason (dict resize) and a supervisor rebuild
    registering an engine (WeakSet resize) mid-iteration — the
    build_heartbeat race class, now fixed at the aggregation layer."""
    from langstream_tpu.providers.jax_local import engine as engine_mod

    class _StubEngine:
        max_slots = 1
        queue_timeout_s = None
        slo = None
        spec = False
        kv_manager = None
        peaks = None
        queue_depth = 0

        def __init__(self):
            self.stats = engine_mod.DecodeEngine._fresh_stats()
            self.stats["tokens_generated"] = 5
            self.stats["decode_steps"] = 5
            self.stats["tokens_useful"] = 4
            self.stats["tokens_wasted"] = _FlakyDict(
                {"cancelled": 1}, fails=2
            )
            self.stats["requests_shed"] = _FlakyDict(fails=2)

    stub = _StubEngine()
    monkeypatch.setattr(
        engine_mod, "_LIVE_ENGINES", _FlakyIterable([stub], fails=2)
    )
    out = engine_mod.engines_snapshot()
    assert out["jax_engine_tokens_generated"] == 5.0
    assert out['jax_engine_tokens_wasted_total{reason="cancelled"}'] == 1.0


# ---------------------------------------------------------------------- #
# repo-wide clean run + CLI gate
# ---------------------------------------------------------------------- #
def test_repo_ast_passes_run_clean():
    """THE acceptance gate: zero unsuppressed findings across the whole
    package from both AST passes — and the audit surface is real (the
    suppressions that exist all carry reasons)."""
    lock = run_lock_pass([PKG])
    jit = run_jit_pass([PKG])
    open_findings = [f for f in lock + jit if not f.suppressed]
    assert not open_findings, "\n".join(f.format() for f in open_findings)
    suppressed = [f for f in lock + jit if f.suppressed]
    # the threaded engine's documented exemptions exist and are reasoned
    assert suppressed, "expected auditable suppressions in the runtime"
    assert all(f.reason for f in suppressed)


def test_no_module_defines_a_top_level_name_twice():
    """Python binds the later ``def`` silently: a shadowed copy is dead
    code that grep still finds and an edit to it changes nothing."""
    import ast as ast_mod

    twice = []
    for root, _, files in os.walk(PKG):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            seen = {}
            for node in ast_mod.parse(open(path).read()).body:
                if isinstance(node, (ast_mod.FunctionDef, ast_mod.ClassDef,
                                     ast_mod.AsyncFunctionDef)):
                    if node.name in seen:
                        twice.append(
                            f"{path}: {node.name} at lines "
                            f"{seen[node.name]} and {node.lineno}"
                        )
                    seen[node.name] = node.lineno
    assert not twice, "\n".join(twice)


def test_the_model_step_and_the_latent_family_import_one_way():
    """``model.py`` (programs, the layer loop, the block body) imports
    ``latent_moe.py`` (an attention kind) at module level and never the
    other way round, and neither imports a provider module from inside a
    function: nothing is held apart by import order."""
    import ast as ast_mod

    package = "langstream_tpu.providers.jax_local"

    def provider_imports(tree):
        for node in ast_mod.walk(tree):
            if isinstance(node, ast_mod.ImportFrom) and node.module:
                names = [f"{node.module}.{alias.name}" for alias in node.names]
            elif isinstance(node, ast_mod.Import):
                names = [alias.name for alias in node.names]
            else:
                continue
            for name in names:
                if name.startswith(package):
                    yield node, name

    trees = {
        name: ast_mod.parse(open(os.path.join(
            PKG, "providers", "jax_local", name + ".py"
        )).read())
        for name in ("model", "latent_moe")
    }
    for name, tree in trees.items():
        top_level = set(map(id, tree.body))
        inside = [
            f"{name}.py:{node.lineno} imports {what} inside a function"
            for node, what in provider_imports(tree)
            if id(node) not in top_level
        ]
        assert not inside, "\n".join(inside)
    assert not [
        what for _, what in provider_imports(trees["latent_moe"])
        if what.startswith(package + ".model")
    ]
    assert [
        what for _, what in provider_imports(trees["model"])
        if what == package + ".latent_moe"
    ]


def test_annotations_cover_the_threaded_core():
    """The annotation work is load-bearing: the core threaded classes
    each declare at least one guarded/owned attribute, so the pass has
    teeth precisely where PRs 8-12 found races by review."""
    import ast as ast_mod

    from langstream_tpu.analysis.common import file_comments
    from langstream_tpu.analysis.lock_discipline import (
        _ClassInfo,
        _collect_annotations,
    )

    expectations = {
        "providers/jax_local/engine.py": "DecodeEngine",
        "runtime/supervisor.py": "EngineSupervisor",
        "runtime/flight.py": "FlightRecorder",
        "fleet/router.py": "FleetRouter",
        "api/metrics.py": "MetricsReporter",
    }
    for rel, cls in expectations.items():
        path = os.path.join(PKG, rel)
        source = open(path).read()
        tree = ast_mod.parse(source)
        node = next(
            n for n in ast_mod.walk(tree)
            if isinstance(n, ast_mod.ClassDef) and n.name == cls
        )
        info = _ClassInfo(node)
        _collect_annotations(info, file_comments(source), path)
        assert info.guarded or info.owned, f"{cls} lost its annotations"


def test_check_cli_gates_on_findings(tmp_path):
    from langstream_tpu.analysis.check import build_parser, run_check

    dirty = _write(tmp_path, """
        import threading

        class Box:
            def __init__(self):
                self._items = []  # guarded-by: _lock
                self._lock = threading.Lock()

            def bad(self):
                self._items.append(1)
    """)
    parser = build_parser()
    # --skip retrace keeps these CLI-contract checks AST-only (the
    # retrace pass builds engines; it has its own tests below)
    fast = ["--skip", "hlo", "--skip", "retrace"]
    assert run_check(parser.parse_args([dirty, *fast])) == 1
    assert run_check(parser.parse_args([PKG, *fast])) == 0
    assert run_check(
        parser.parse_args([dirty, *fast, "--json"])
    ) == 1
    # a typo'd path must fail loudly, never gate CLEAN over zero files
    assert run_check(
        parser.parse_args([str(tmp_path / "nope"), *fast])
    ) == 2
    # ... and so must an existing directory with no Python in it
    empty = tmp_path / "empty"
    empty.mkdir()
    assert run_check(
        parser.parse_args([str(empty), *fast])
    ) == 2
