"""tracelint test suite (ISSUE 5): per-rule fixtures — true positive,
true negative, suppressed — plus the tier-1 CI gate: a self-run over
``mxnet_tpu/`` must be clean, and a synthetic ``float(loss)`` seeded
into a fused-step body must fail it.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.tracelint import run_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def lint(tmp_path, source, name="snippet.py", **kw):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return run_paths([str(p)], **kw)


def rules_of(findings):
    return [f.rule for f in findings]


def cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.tracelint"] + args,
        capture_output=True, text=True, cwd=cwd, env=_ENV)


# ------------------------------------------------------------------ #
# TL001 — host sync inside traced code
# ------------------------------------------------------------------ #

class TestTL001HostSync:
    def test_float_in_jitted_fn(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def step(w, g):
                lr = float(g)
                return w - lr * g

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TL001"]
        assert "float" in fs[0].message and "step" in fs[0].message

    def test_item_via_callgraph_helper(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def helper(x):
                return x.item()

            def step(x):
                return helper(x)

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TL001"]
        assert "helper" in fs[0].message

    def test_branch_on_traced_array(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import jax.numpy as jnp

            def step(x):
                s = jnp.sum(x)
                if s > 0:
                    return x
                return -x

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TL001"]
        assert "branches on a traced array" in fs[0].message

    def test_numpy_materialization_in_trace_scope(self, tmp_path):
        fs = lint(tmp_path, """
            import numpy as onp
            from mxnet_tpu.gluon.block import trace_scope

            def run(key, vals):
                with trace_scope(key, True) as aux:
                    host = onp.asarray(vals[0])
                return host
        """)
        assert rules_of(fs) == ["TL001"]
        assert "onp.asarray" in fs[0].message

    def test_true_negatives(self, tmp_path):
        # host work outside the traced region, trace-time python on
        # hyperparameters/shapes, identity tests: all fine
        fs = lint(tmp_path, """
            import jax
            import jax.numpy as jnp

            def host_metric(x):
                return float(x)  # never traced

            class Rule:
                momentum = 0.0

                def step(self, w, g, state):
                    n = float(w.shape[0])
                    if self.momentum == 0.0:
                        return w - g / n
                    if state is None:
                        state = jnp.zeros_like(w)
                    return w + self.momentum * state - g / n

            def outer(w, g, s):
                return Rule().step(w, g, s)

            fn = jax.jit(outer)
        """)
        assert fs == []

    def test_suppressed_with_reason(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def step(w, g):
                lr = float(g)  # tracelint: disable=TL001 -- test fixture
                return w - lr * g

            fn = jax.jit(step)
        """)
        assert fs == []

    def test_suppression_without_reason_is_tl000_and_keeps_finding(
            self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def step(w, g):
                lr = float(g)  # tracelint: disable=TL001
                return w - lr * g

            fn = jax.jit(step)
        """)
        assert sorted(rules_of(fs)) == ["TL000", "TL001"]


# ------------------------------------------------------------------ #
# TL002 — donated buffer read after dispatch
# ------------------------------------------------------------------ #

class TestTL002Donation:
    def test_read_after_donating_dispatch(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def add(a, b):
                return a + b

            def outer(w, g):
                fn = jax.jit(add, donate_argnums=(0,))
                out = fn(w, g)
                return w + out
        """)
        assert rules_of(fs) == ["TL002"]
        assert "`w`" in fs[0].message

    def test_producer_method_indirection(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def add(a, b):
                return a + b

            class Step:
                def _make(self):
                    return jax.jit(add, donate_argnums=(1,))

                def run(self, w, g):
                    fn = self._make()
                    out = fn(w, g)
                    return g + out
        """)
        assert rules_of(fs) == ["TL002"]
        assert "`g`" in fs[0].message

    def test_rebind_from_result_is_fine(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def add(a, b):
                return a + b

            def outer(w, g):
                fn = jax.jit(add, donate_argnums=(0,))
                w = fn(w, g)
                return w + 1
        """)
        assert fs == []

    def test_phase_polymorphic_producer_intersects(self, tmp_path):
        # the FusedStep._compile regression: a compiler returning
        # different jits per phase must not union donated positions
        fs = lint(tmp_path, """
            import jax

            def add(a, b):
                return a + b

            class Step:
                def _make(self, phase):
                    if phase == "micro":
                        return jax.jit(add, donate_argnums=(0,))
                    return jax.jit(add, donate_argnums=(1,))

                def run(self, w, g):
                    fn = self._make("micro")
                    out = fn(w, g)
                    return w + g + out
        """)
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def add(a, b):
                return a + b

            def outer(w, g):
                fn = jax.jit(add, donate_argnums=(0,))
                out = fn(w, g)
                return w + out  # tracelint: disable=TL002 -- fixture
        """)
        assert fs == []


# ------------------------------------------------------------------ #
# TL003 — retrace hazards
# ------------------------------------------------------------------ #

class TestTL003Retrace:
    def test_list_in_cache_key(self, tmp_path):
        fs = lint(tmp_path, """
            def lookup(cache, shape):
                opts = [shape]
                key = (shape, opts)
                return cache.get(key)
        """)
        assert rules_of(fs) == ["TL003"]
        assert "a list" in fs[0].message

    def test_lambda_and_id_keys(self, tmp_path):
        fs = lint(tmp_path, """
            def store(cache, f, shape):
                cache[(shape, lambda x: x)] = 1
                cache[(id(f), shape)] = 2
        """)
        assert sorted(rules_of(fs)) == ["TL003", "TL003"]
        msgs = " ".join(f.message for f in fs)
        assert "lambda" in msgs and "identity key" in msgs

    def test_jit_inside_loop(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def build(fns):
                outs = []
                for f in fns:
                    outs.append(jax.jit(f))
                return outs
        """)
        assert "TL003" in rules_of(fs)
        assert "inside a loop" in fs[0].message

    def test_hashable_key_and_hoisted_jit_are_fine(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def get(cache, arr, training, hyper_key):
                key = (tuple(arr.shape), str(arr.dtype), training,
                       hyper_key)
                fn = cache.get(key)
                if fn is None:
                    fn = jax.jit(lambda x: x + 1)
                    cache[key] = fn
                return fn
        """)
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            def store(cache, f, shape):
                # bounded registry, evicted on pickle:
                # tracelint: disable=TL003 -- fixture justification
                cache[(id(f), shape)] = 2
        """)
        assert fs == []


# ------------------------------------------------------------------ #
# TL004 — lock discipline
# ------------------------------------------------------------------ #

class TestTL004Locks:
    def test_unlocked_mutation_of_protected_field(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Ring:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def push(self, x):
                    with self._lock:
                        self._items.append(x)

                def drop(self):
                    self._items.clear()
        """)
        assert rules_of(fs) == ["TL004"]
        assert "_items" in fs[0].message and "drop" in fs[0].message

    def test_lock_order_inversion(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class AB:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self._x = 0

                def one(self):
                    with self._a:
                        with self._b:
                            self._x = 1

                def two(self):
                    with self._b:
                        with self._a:
                            self._x = 2
        """)
        assert rules_of(fs) == ["TL004"]
        assert "inversion" in fs[0].message

    def test_consistent_locking_and_init_are_fine(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Ring:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []      # pre-sharing: exempt

                def push(self, x):
                    with self._lock:
                        self._items.append(x)

                def drop(self):
                    with self._lock:
                        self._items.clear()

                def peek(self):
                    return len(self._items)  # read, not mutation
        """)
        assert fs == []

    def test_module_level_lock(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            _lock = threading.Lock()
            _registry = {}

            def put(k, v):
                with _lock:
                    _registry[k] = v

            def drop(k):
                _registry.pop(k)
        """)
        assert rules_of(fs) == ["TL004"]
        assert "_registry" in fs[0].message

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Ring:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def push(self, x):
                    with self._lock:
                        self._items.append(x)

                def drop(self):
                    self._items.clear()  # tracelint: disable=TL004 -- fixture
        """)
        assert fs == []


# ------------------------------------------------------------------ #
# TL005 — env-hatch registry
# ------------------------------------------------------------------ #

class TestTL005EnvRegistry:
    def _docs(self, tmp_path):
        d = tmp_path / "docs"
        d.mkdir(exist_ok=True)
        f = d / "ENV_VARS.md"
        f.write_text("| Variable | Default | Effect |\n|---|---|---|\n"
                     "| `MXNET_DOCUMENTED` | 1 | real |\n"
                     "| `MXNET_STALE` | 1 | nobody reads me |\n")
        return str(f)

    def test_undocumented_read_and_stale_row(self, tmp_path):
        docs = self._docs(tmp_path)
        fs = lint(tmp_path, """
            import os

            a = os.environ.get("MXNET_DOCUMENTED", "1")
            b = os.environ.get("MXNET_SECRET", "0")
        """, env_docs=docs)
        assert sorted(rules_of(fs)) == ["TL005", "TL005"]
        msgs = " ".join(f.message for f in fs)
        assert "MXNET_SECRET" in msgs and "MXNET_STALE" in msgs
        assert "MXNET_DOCUMENTED" not in msgs

    def test_registered_and_documented_is_clean(self, tmp_path):
        d = tmp_path / "docs"
        d.mkdir()
        (d / "ENV_VARS.md").write_text("| `MXNET_IGNORED_COMPAT` | 1 | "
                                       "accepted, no-op |\n")
        fs = lint(tmp_path, """
            from mxnet_tpu.base import register_env

            register_env("MXNET_IGNORED_COMPAT", 1, "no-op")
        """, env_docs=str(d / "ENV_VARS.md"))
        assert fs == []

    def test_prose_mentions_are_not_documentation(self, tmp_path):
        # a var named in a row's PROSE cell (not the first cell) is a
        # reference, not a doc row — it must not mask a stale/missing row
        d = tmp_path / "docs"
        d.mkdir()
        (d / "ENV_VARS.md").write_text(
            "| `MXNET_REAL` | 1 | replaces `MXNET_LEGACY_PROSE` |\n")
        fs = lint(tmp_path, """
            import os

            a = os.environ.get("MXNET_REAL")
        """, env_docs=str(d / "ENV_VARS.md"))
        assert fs == []


# ------------------------------------------------------------------ #
# the tier-1 gate: self-run, seeded violation, baseline
# ------------------------------------------------------------------ #

class TestGate:
    def test_self_run_is_clean(self):
        """THE CI gate: tracelint over the library AND the tooling and
        benchmark layers — and the runnable example fixtures — must
        stay clean at merge: a regression in trace/sharding discipline
        fails tier-1.  Runs with --jobs to exercise the parallel path
        in CI."""
        r = cli(["mxnet_tpu/", "tools/", "benchmark/",
                 "tests/fixtures/", "--jobs", "2", "--format=json"])
        assert r.returncode == 0, f"tracelint found:\n{r.stdout}\n{r.stderr}"
        payload = json.loads(r.stdout)
        assert payload["findings"] == []

    def test_no_reasonless_suppressions_repo_wide(self):
        """Every `# tracelint: disable=` in the repo — library, tools,
        benchmarks, tests, examples — carries a justification (zero
        TL000s), so nothing is suppressed silently."""
        r = cli(["mxnet_tpu/", "tools/", "benchmark/", "tests/",
                 "example/", "bench.py", "--select", "TL000",
                 "--format=json"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["findings"] == []

    def test_seeded_float_loss_fails_gate(self, tmp_path):
        """Acceptance check: a synthetic host sync in a fused-step body
        is caught (the analyzer sees through jax.jit(apply, ...))."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "gluon", "fused_step.py")).read()
        needle = ("            outs, grads, new_frozen = "
                  "pure(key, train_vals, frozen_vals,\n")
        assert needle in src
        seeded = src.replace(
            needle, needle.rstrip("\n") + "\n                loss_val = "
            "float(outs[0])  # seeded violation\n", 1)
        bad = tmp_path / "fused_step_seeded.py"
        bad.write_text(seeded)
        r = cli([str(bad), "--format=json"])
        assert r.returncode == 1
        payload = json.loads(r.stdout)
        assert any(f["rule"] == "TL001" and "float" in f["message"]
                   for f in payload["findings"])

    def test_seeded_axis_mismatch_fails_gate(self, tmp_path):
        """Acceptance check: an axis-name literal drifted away from the
        collectives' axis vocabulary is caught (TL006)."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "parallel", "collectives.py")).read()
        needle = "        return jax.lax.psum(contrib, axis)"
        assert needle in src
        seeded = src.replace(
            needle, '        return jax.lax.psum(contrib, "dcn")', 1)
        bad = tmp_path / "collectives_seeded.py"
        bad.write_text(seeded)
        r = cli([str(bad), "--format=json"])
        assert r.returncode == 1
        hits = [f for f in json.loads(r.stdout)["findings"]
                if f["rule"] == "TL006"]
        assert hits and "'dcn'" in hits[0]["message"]
        assert hits[0]["severity"] == "error"

    def test_seeded_conditional_collective_fails_gate(self, tmp_path):
        """Acceptance check: a collective gated on jax.process_index()
        inside the pipeline's traced shard body is caught (TL008)."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "parallel", "pipeline.py")).read()
        needle = "        my = lax.axis_index(axis)\n"
        assert needle in src
        seeded = src.replace(
            needle, needle +
            "        if jax.process_index() == 0:\n"
            "            xs_local = lax.psum(xs_local, axis)\n", 1)
        bad = tmp_path / "pipeline_seeded.py"
        bad.write_text(seeded)
        r = cli([str(bad), "--select", "TL008", "--format=json"])
        assert r.returncode == 1
        hits = json.loads(r.stdout)["findings"]
        assert any("psum" in f["message"] and
                   "host-dependent" in f["message"] for f in hits)

    def test_baseline_lands_rule_warn_only(self, tmp_path):
        """--baseline lets a future rule land without failing the gate:
        recorded fingerprints are ignored, fresh findings are not."""
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent("""
            import jax

            def step(w, g):
                lr = float(g)
                return w - lr * g

            fn = jax.jit(step)
        """))
        base = tmp_path / "baseline.json"
        r = cli([str(bad), "--write-baseline", str(base)])
        assert r.returncode == 0 and base.exists()
        r = cli([str(bad), "--baseline", str(base)])
        assert r.returncode == 0, r.stdout
        # a NEW violation is still caught through the same baseline
        bad.write_text(bad.read_text().replace(
            "return w - lr * g", "return w - lr * g.item()"))
        r = cli([str(bad), "--baseline", str(base), "--format=json"])
        assert r.returncode == 1
        assert any(f["rule"] == "TL001" and "item" in f["message"]
                   for f in json.loads(r.stdout)["findings"])

    def test_select_restricts_rules(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent("""
            import jax

            def step(w, g):
                return w - float(g) * g

            fn = jax.jit(step)
        """))
        assert cli([str(bad), "--select", "TL004"]).returncode == 0
        assert cli([str(bad), "--select", "TL001"]).returncode == 1
        assert cli([str(bad), "--select", "TL999"]).returncode == 2


class TestReviewRegressions:
    """Post-review regression net: partial-tree TL005, nested-class
    TL004 attribution, suppression markers inside string literals."""

    def test_single_file_lint_has_no_stale_doc_false_positives(self):
        # the natural lint-the-file-I-edited workflow: env vars read
        # elsewhere in the repo must not be reported as stale doc rows
        r = cli(["mxnet_tpu/gluon/data/dataloader.py", "--format=json"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["findings"] == []

    def test_nested_class_owns_its_own_lock_discipline(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Outer:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def push(self, x):
                    with self._lock:
                        self._items.append(x)

                class Inner:  # unrelated single-threaded helper
                    def __init__(self):
                        self._items = []

                    def drop(self):
                        self._items.clear()
        """)
        assert fs == []

    def test_suppression_marker_inside_string_is_not_a_suppression(
            self, tmp_path):
        # core.py's own TL000 help text quotes the syntax; a string
        # must neither raise TL000 nor suppress the next line
        fs = lint(tmp_path, """
            import jax

            HELP = "write '# tracelint: disable=TLxxx -- reason'"

            def step(w, g):
                msg = "see '# tracelint: disable=TL001 -- like this'"
                lr = float(g)
                return w - lr * g

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TL001"]

    def test_self_lint_of_tracelint_itself(self):
        # the analyzer's own sources (which quote the suppression
        # syntax in strings/docstrings) must lint clean
        r = cli(["tools/tracelint/", "--format=json"])
        assert r.returncode == 0, r.stdout


# ------------------------------------------------------------------ #
# cross-module call-graph resolution (ISSUE 11 engine upgrade)
# ------------------------------------------------------------------ #

def lint_tree(tmp_path, files, **kw):
    for name, source in files.items():
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(source))
    return run_paths([str(tmp_path)], **kw)


class TestCrossModuleEngine:
    def test_tl001_reaches_host_sync_two_modules_away(self, tmp_path):
        """THE regression pin for the repo-wide engine: the jit seed in
        a.py propagates through b.py into c.py's host sync."""
        fs = lint_tree(tmp_path, {
            "a.py": """
                import jax
                from b import step

                fn = jax.jit(step)
            """,
            "b.py": """
                from c import helper

                def step(x):
                    return helper(x)
            """,
            "c.py": """
                def helper(x):
                    return x.item()
            """})
        assert rules_of(fs) == ["TL001"]
        assert fs[0].path.endswith("c.py")
        assert "helper" in fs[0].message

    def test_from_import_aliasing(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "a.py": """
                import jax
                from b import step as entry

                fn = jax.jit(entry)
            """,
            "b.py": """
                def step(x):
                    return float(x)
            """})
        assert rules_of(fs) == ["TL001"]
        assert fs[0].path.endswith("b.py")

    def test_module_dotted_seed(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "a.py": """
                import jax
                import b

                fn = jax.jit(b.step)
            """,
            "b.py": """
                def step(x):
                    return x.asnumpy()
            """})
        assert rules_of(fs) == ["TL001"]
        assert fs[0].path.endswith("b.py")

    def test_relative_import_chain_in_package(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/a.py": """
                import jax
                from .b import step

                fn = jax.jit(step)
            """,
            "pkg/b.py": """
                from .c import helper

                def step(x):
                    return helper(x)
            """,
            "pkg/c.py": """
                def helper(x):
                    return x.tolist()
            """})
        assert rules_of(fs) == ["TL001"]
        assert fs[0].path.endswith(os.path.join("pkg", "c.py"))

    def test_reexport_through_package_init(self, tmp_path):
        # `from pkg import helper` where pkg/__init__ re-exports it
        fs = lint_tree(tmp_path, {
            "pkg/__init__.py": "from .impl import helper\n",
            "pkg/impl.py": """
                def helper(x):
                    return x.item()
            """,
            "main.py": """
                import jax
                from pkg import helper

                def step(x):
                    return helper(x)

                fn = jax.jit(step)
            """})
        assert rules_of(fs) == ["TL001"]
        assert fs[0].path.endswith("impl.py")

    def test_diamond_imports_flag_once(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "base.py": """
                def helper(x):
                    return x.item()
            """,
            "left.py": """
                from base import helper

                def via_left(x):
                    return helper(x)
            """,
            "right.py": """
                from base import helper

                def via_right(x):
                    return helper(x)
            """,
            "top.py": """
                import jax
                from left import via_left
                from right import via_right

                def step(x):
                    return via_left(x) + via_right(x)

                fn = jax.jit(step)
            """})
        assert rules_of(fs) == ["TL001"]  # one finding, not two
        assert fs[0].path.endswith("base.py")

    def test_unresolvable_import_falls_back_to_module_local(
            self, tmp_path):
        # an import the project can't see contributes no edges; the
        # module-local walk still catches the local violation
        fs = lint_tree(tmp_path, {
            "a.py": """
                import jax
                from some_external_dep import helper

                def step(x):
                    y = helper(x)
                    return float(y)

                fn = jax.jit(step)
            """})
        assert rules_of(fs) == ["TL001"]
        assert "float" in fs[0].message

    def test_class_method_resolution_across_modules(self, tmp_path):
        # ancestor direction: traced Sub.step calls self.helper defined
        # on a base class imported from another module
        fs = lint_tree(tmp_path, {
            "base_mod.py": """
                class Base:
                    def helper(self, x):
                        return x.item()
            """,
            "sub_mod.py": """
                import jax
                from base_mod import Base

                class Sub(Base):
                    @jax.jit
                    def step(self, x):
                        return self.helper(x)
            """})
        assert rules_of(fs) == ["TL001"]
        assert fs[0].path.endswith("base_mod.py")

    def test_subclass_override_across_modules(self, tmp_path):
        # descendant direction: traced Base.run calls self.rule, which
        # a subclass in ANOTHER module overrides with a host sync (the
        # optimizer-registry pattern, now cross-file)
        fs = lint_tree(tmp_path, {
            "base_mod.py": """
                import jax

                class Base:
                    @jax.jit
                    def run(self, x):
                        return self.rule(x)

                    def rule(self, x):
                        return x
            """,
            "sub_mod.py": """
                from base_mod import Base

                class Sub(Base):
                    def rule(self, x):
                        return float(x)
            """})
        assert rules_of(fs) == ["TL001"]
        assert fs[0].path.endswith("sub_mod.py")

    def test_partial_wrapped_seed(self, tmp_path):
        # shard_map(partial(fn, ...)) traces fn
        fs = lint_tree(tmp_path, {
            "a.py": """
                import jax
                from functools import partial

                def body(v, flag):
                    return v.item()

                fn = jax.shard_map(partial(body, flag=True), mesh=None,
                                   in_specs=None, out_specs=None)
            """})
        assert rules_of(fs) == ["TL001"]

    def test_local_variable_sharing_a_module_name_stays_unresolved(
            self, tmp_path):
        # review regression: `bench = Bench(); bench.run(x)` must NOT
        # resolve into a lint module named bench.py — a plain variable
        # receiver is not an import binding
        fs = lint_tree(tmp_path, {
            "bench.py": """
                def run(x):
                    return float(x)
            """,
            "a.py": """
                import jax
                from somewhere import Bench

                def step(x):
                    bench = Bench()
                    return bench.run(x)

                fn = jax.jit(step)
            """})
        assert fs == []

    def test_symbol_abstract_eval_does_not_trace_invoke(self):
        """Regression for the cross-module finding fixed in this PR:
        symbol's eval_shape bodies route through _node_outputs_abstract
        (raw opref.fn), NOT _registry.invoke, so the imperative
        machinery (profiler clocks, NaiveEngine block_until_ready, env
        hatches via is_naive_engine) is no longer trace-reachable."""
        r = cli(["mxnet_tpu/symbol/symbol.py", "mxnet_tpu/ops/registry.py",
                 "mxnet_tpu/base.py", "--select", "TL001,TL007",
                 "--format=json"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["findings"] == []


# ------------------------------------------------------------------ #
# TL006 — axis/mesh discipline
# ------------------------------------------------------------------ #

class TestTL006AxisDiscipline:
    def test_unknown_axis_cross_module_is_error(self, tmp_path):
        # the binding mesh lives in one module, the drifted literal in
        # another — the exact seam the module-local engine missed
        fs = lint_tree(tmp_path, {
            "mesh_mod.py": """
                import numpy as onp
                from jax.sharding import Mesh

                MESH = Mesh(onp.arange(4), ("dp",))
            """,
            "use_mod.py": """
                from jax import lax

                def reduce_grads(g):
                    return lax.psum(g, "pd")
            """})
        assert rules_of(fs) == ["TL006"]
        assert fs[0].severity == "error"
        assert "'pd'" in fs[0].message and fs[0].path.endswith("use_mod.py")

    def test_bound_axis_is_clean(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "mesh_mod.py": """
                import numpy as onp
                from jax.sharding import Mesh

                MESH = Mesh(onp.arange(8).reshape(4, 2), ("dp", "tp"))
            """,
            "use_mod.py": """
                from jax import lax
                from jax.sharding import PartitionSpec

                def reduce_grads(g):
                    return lax.psum(g, "tp")

                SPEC = PartitionSpec("dp", None)
            """})
        assert fs == []

    def test_param_default_only_axis_literal_is_warn(self, tmp_path):
        # 'sp' exists only as a default-axis parameter: a literal use is
        # conditionally bound (depends on the caller's mesh) — warn
        fs = lint_tree(tmp_path, {
            "api.py": """
                from jax import lax

                def ring_pass(x, axis="sp"):
                    return lax.ppermute(x, axis_name=axis, perm=[])
            """,
            "use.py": """
                from jax import lax

                def fold(x):
                    return lax.psum(x, "sp")
            """})
        assert rules_of(fs) == ["TL006"]
        assert fs[0].severity == "warn"
        assert "conditionally bound" in fs[0].message

    def test_make_mesh_dict_binds_axes(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "a.py": """
                from jax import lax
                from mylib import make_mesh

                MESH = make_mesh({"dp": 4, "sp": 2})

                def fold(x):
                    return lax.psum(x, ("dp", "sp"))
            """})
        assert fs == []

    def test_partition_spec_unknown_axis(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "a.py": """
                import numpy as onp
                from jax.sharding import Mesh, PartitionSpec as P

                MESH = Mesh(onp.arange(4), ("dp",))
                SPEC = P("model", None)
            """})
        assert rules_of(fs) == ["TL006"]
        assert "PartitionSpec" in fs[0].message
        assert "'model'" in fs[0].message

    def test_gather_axis_kwarg_does_not_shadow_axis_name(self, tmp_path):
        # review regression: all_gather's axis= kwarg is the INTEGER
        # array dim; the positional axis NAME must still be checked
        fs = lint_tree(tmp_path, {
            "mesh_mod.py": """
                import numpy as onp
                from jax.sharding import Mesh

                MESH = Mesh(onp.arange(4), ("dp",))
            """,
            "use_mod.py": """
                from jax import lax

                def gather(x):
                    return lax.all_gather(x, "dcn", axis=0, tiled=True)
            """})
        assert rules_of(fs) == ["TL006"]
        assert "'dcn'" in fs[0].message

    def test_suppressed(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "a.py": """
                from jax import lax

                def fold(x):
                    # tracelint: disable=TL006 -- fixture: axis bound by caller's test mesh
                    return lax.psum(x, "zz")
            """})
        assert fs == []


# ------------------------------------------------------------------ #
# TL007 — cross-host trace divergence
# ------------------------------------------------------------------ #

class TestTL007HostDivergence:
    def test_process_index_feeding_return(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def step(x):
                r = jax.process_index()
                return x + r

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TL007"]
        assert "process_index" in fs[0].message

    def test_environ_branching_the_trace(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import os

            def step(x):
                if os.environ.get("MXNET_DEBUG_SCALE"):
                    return x * 2
                return x

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TL007"]
        assert "environ" in fs[0].message

    def test_host_rng_feeding_jax_call(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import numpy as onp

            def step(x):
                key = jax.random.PRNGKey(onp.random.randint(0, 100))
                return x + jax.random.uniform(key, x.shape)

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TL007"]
        assert "host RNG" in fs[0].message

    def test_from_imported_host_reads_are_caught(self, tmp_path):
        # review regression: `from os import getenv` / `from time
        # import perf_counter` classify the same as the dotted forms
        fs = lint(tmp_path, """
            import jax
            from os import getenv

            def step(x):
                if getenv("MXNET_DEBUG_SCALE"):
                    return x * 2
                return x

            fn = jax.jit(step)
        """)
        assert rules_of(fs) == ["TL007"]

    def test_project_module_named_random_is_not_stdlib(self, tmp_path):
        # `from pkg import random` binds a PROJECT module; its draws are
        # jax-keyed, not host RNG — must not classify as stdlib random
        fs = lint_tree(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/random.py": """
                def uniform(key, shape):
                    return shape
            """,
            "pkg/use.py": """
                import jax
                from . import random

                def step(x):
                    return x + random.uniform(None, x.shape)

                fn = jax.jit(step)
            """})
        assert [f for f in fs if f.rule == "TL007"] == []

    def test_host_side_timer_is_not_divergence(self, tmp_path):
        # a profiler clock whose value never feeds the trace (the
        # registry.invoke pattern): no finding
        fs = lint(tmp_path, """
            import jax
            import time

            def log_ms(dt):
                pass

            def step(x):
                t0 = time.perf_counter()
                y = x + 1
                if t0 is not None:
                    log_ms(time.perf_counter() - t0)
                return y

            fn = jax.jit(step)
        """)
        assert fs == []

    def test_process_index_outside_trace_is_fine(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def rank():
                return jax.process_index()
        """)
        assert fs == []

    def test_donate_argnums_from_set_iteration(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def f(a, b):
                return a + b

            fn = jax.jit(f, donate_argnums=tuple({0, 1}))
        """)
        assert rules_of(fs) == ["TL007"]
        assert "donate_argnums" in fs[0].message

    def test_sorted_set_is_stable(self, tmp_path):
        fs = lint(tmp_path, """
            import jax

            def f(a, b):
                return a + b

            fn = jax.jit(f, donate_argnums=tuple(sorted({0, 1})))
        """)
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import os

            def step(x):
                # tracelint: disable=TL007 -- fixture: launcher propagates env
                if os.environ.get("MXNET_DEBUG_SCALE"):
                    return x * 2
                return x

            fn = jax.jit(step)
        """)
        assert fs == []


# ------------------------------------------------------------------ #
# TL008 — conditional collectives
# ------------------------------------------------------------------ #

class TestTL008ConditionalCollective:
    def test_collective_under_data_dependent_branch(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import jax.numpy as jnp
            from jax import lax

            def body(v):
                s = jnp.sum(v)
                if s > 0:
                    v = lax.psum(v, "dp")
                return v

            fn = jax.shard_map(body, mesh=None, in_specs=None,
                               out_specs=None)
        """, select=["TL008"])
        assert rules_of(fs) == ["TL008"]
        assert "data-dependent" in fs[0].message
        assert "psum" in fs[0].message

    def test_collective_under_host_dependent_branch(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            from jax import lax

            def body(v):
                if jax.process_index() == 0:
                    v = lax.psum(v, "dp")
                return v

            fn = jax.shard_map(body, mesh=None, in_specs=None,
                               out_specs=None)
        """, select=["TL008"])
        assert rules_of(fs) == ["TL008"]
        assert "host-dependent" in fs[0].message

    def test_collective_under_static_config_branch_is_fine(
            self, tmp_path):
        # a trace-time hyperparameter branch is uniform across shards
        fs = lint(tmp_path, """
            import jax
            from jax import lax

            def make(reduce_grads):
                def body(v):
                    if reduce_grads:
                        v = lax.psum(v, "dp")
                    return v
                return jax.shard_map(body, mesh=None, in_specs=None,
                                     out_specs=None)
        """, select=["TL008"])
        assert fs == []

    def test_collective_in_loop_is_fine(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            from jax import lax

            def body(v):
                for i in range(4):
                    v = lax.ppermute(v, "sp", [(0, 1), (1, 0)])
                return v

            fn = jax.shard_map(body, mesh=None, in_specs=None,
                               out_specs=None)
        """, select=["TL008"])
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            import jax.numpy as jnp
            from jax import lax

            def body(v):
                s = jnp.sum(v)
                if s > 0:
                    # tracelint: disable=TL008 -- fixture justification
                    v = lax.psum(v, "dp")
                return v

            fn = jax.shard_map(body, mesh=None, in_specs=None,
                               out_specs=None)
        """, select=["TL008"])
        assert fs == []


# ------------------------------------------------------------------ #
# TL009 — accountant discipline
# ------------------------------------------------------------------ #

class TestTL009AccountantDiscipline:
    def test_set_without_drop(self, tmp_path):
        fs = lint(tmp_path, """
            from mxnet_tpu.telemetry.memory import ACCOUNTANT

            def hold(key, tree):
                ACCOUNTANT.set("serve.scratch", key, tree)
        """, select=["TL009"])
        assert rules_of(fs) == ["TL009"]
        assert "serve.scratch" in fs[0].message

    def test_drop_in_another_module_pairs(self, tmp_path):
        # the release path may live across the repo (Trainer sets,
        # FusedStep drops) — project-wide pairing, no finding
        fs = lint_tree(tmp_path, {
            "a.py": """
                from mxnet_tpu.telemetry.memory import ACCOUNTANT

                def hold(key, tree):
                    ACCOUNTANT.set("serve.scratch", key, tree)
            """,
            "b.py": """
                from mxnet_tpu.telemetry.memory import ACCOUNTANT

                def release(key):
                    ACCOUNTANT.drop_deferred("serve.scratch", key)
            """}, select=["TL009"])
        assert fs == []

    def test_dynamic_subsystem_is_skipped(self, tmp_path):
        fs = lint(tmp_path, """
            from mxnet_tpu.telemetry.memory import ACCOUNTANT

            def hold(subsystem, key, tree):
                ACCOUNTANT.set(subsystem, key, tree)
        """, select=["TL009"])
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            from mxnet_tpu.telemetry.memory import ACCOUNTANT

            def hold(key, tree):
                # tracelint: disable=TL009 -- fixture: process-lifetime entry
                ACCOUNTANT.set("proc.forever", key, tree)
        """, select=["TL009"])
        assert fs == []


# ------------------------------------------------------------------ #
# TL010 — stale suppressions (opt-in)
# ------------------------------------------------------------------ #

class TestTL010StaleSuppressions:
    SRC = """
        import jax

        def step(w, g):
            lr = float(g)  # tracelint: disable=TL001 -- epoch sync fixture
            return w - lr * g

        def host_only(x):
            return x + 1  # tracelint: disable=TL002 -- stale: nothing fires here

        fn = jax.jit(step)
    """

    def test_stale_suppression_reported_on_select(self, tmp_path):
        fs = lint(tmp_path, self.SRC, select=["TL010"])
        assert rules_of(fs) == ["TL010"]
        assert "TL002" in fs[0].message
        assert fs[0].severity == "warn"

    def test_live_suppression_not_reported(self, tmp_path):
        fs = lint(tmp_path, self.SRC, select=["TL010"])
        assert all("TL001" not in f.message for f in fs)

    def test_not_reported_by_default(self, tmp_path):
        fs = lint(tmp_path, self.SRC)
        assert fs == []

    def test_repo_has_no_stale_suppressions(self):
        r = cli(["mxnet_tpu/", "tools/", "benchmark/", "--select",
                 "TL010", "--format=json"])
        assert json.loads(r.stdout)["findings"] == []


# ------------------------------------------------------------------ #
# TL011 — clock discipline
# ------------------------------------------------------------------ #

class TestTL011ClockDiscipline:
    def test_wall_clock_deadline_math(self, tmp_path):
        fs = lint(tmp_path, """
            import time

            def close(timeout=60.0):
                deadline = time.time() + timeout
                while time.time() < deadline:
                    pass
        """, select=["TL011"])
        assert set(rules_of(fs)) == {"TL011"}
        # one finding per defect: the assignment's BinOp hit subsumes
        # the stored-into hit, the while-compare is the second defect
        assert len(fs) == 2
        msgs = " ".join(f.message for f in fs)
        assert "monotonic" in msgs and "timeout" in msgs

    def test_wall_clock_into_timeout_kwarg(self, tmp_path):
        fs = lint(tmp_path, """
            import time

            def wait_for(ev):
                ev.wait(timeout=time.time())
        """, select=["TL011"])
        assert rules_of(fs) == ["TL011"]
        assert "timeout=" in fs[0].message

    def test_from_imported_time_classifies(self, tmp_path):
        fs = lint(tmp_path, """
            from time import time

            def budget(timeout):
                return time() + timeout
        """, select=["TL011"])
        assert rules_of(fs) == ["TL011"]

    def test_elapsed_logging_is_exempt(self, tmp_path):
        # the event_handler.py / callback.py / telemetry-timestamp
        # exemption: wall-clock elapsed that only feeds logging
        fs = lint(tmp_path, """
            import time

            def log(x):
                pass

            class Speedometer:
                def __init__(self, batch_size):
                    self.batch_size = batch_size
                    self.tic = time.time()

                def __call__(self, count):
                    speed = count * self.batch_size / (
                        time.time() - self.tic)
                    log(speed)
                    self.tic = time.time()

            def stamp(fields):
                return {"ts": round(time.time(), 6), **fields}
        """, select=["TL011"])
        assert fs == []

    def test_monotonic_deadlines_are_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import time

            def close(timeout=60.0):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    pass
        """, select=["TL011"])
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import time

            def lease(timeout):
                # tracelint: disable=TL011 -- fixture: protocol wants wall-clock epoch
                return time.time() + timeout
        """, select=["TL011"])
        assert fs == []


# ------------------------------------------------------------------ #
# TL012 — finalizer lock safety
# ------------------------------------------------------------------ #

class TestTL012FinalizerLocks:
    def test_del_reaches_lock_through_helper(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Ring:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def close(self):
                    with self._lock:
                        self._items.clear()

                def __del__(self):
                    self.close()
        """, select=["TL012"])
        assert rules_of(fs) == ["TL012"]
        assert "__del__" in fs[0].message and "Lock" in fs[0].message

    def test_weakref_finalize_callback(self, tmp_path):
        fs = lint(tmp_path, """
            import threading
            import weakref

            _lock = threading.Lock()
            _reg = {}

            def _cleanup(key):
                with _lock:
                    _reg.pop(key, None)

            class Owner:
                def __init__(self, key):
                    weakref.finalize(self, _cleanup, key)
        """, select=["TL012"])
        assert rules_of(fs) == ["TL012"]
        assert "finalize" in fs[0].message

    def test_aliased_weakref_finalize_is_seen(self, tmp_path):
        # review regression: `import weakref as wr` must classify the
        # same as the plain import; a project-local function named
        # finalize must NOT seed the walk
        fs = lint(tmp_path, """
            import threading
            import weakref as wr

            _lock = threading.Lock()
            _reg = {}

            def _cleanup(key):
                with _lock:
                    _reg.pop(key, None)

            def finalize(obj, fn):   # unrelated local helper
                pass

            class Owner:
                def __init__(self, key):
                    wr.finalize(self, _cleanup, key)

            def harmless(x):
                finalize(x, _cleanup)
        """, select=["TL012"])
        assert rules_of(fs) == ["TL012"]

    def test_singleton_instance_method_resolves(self, tmp_path):
        # the ACCOUNTANT shape: the lock lives behind a module-level
        # singleton in another module
        fs = lint_tree(tmp_path, {
            "ledger.py": """
                import threading

                class Ledger:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._entries = {}

                    def drop(self, key):
                        with self._lock:
                            self._entries.pop(key, None)

                LEDGER = Ledger()
            """,
            "owner.py": """
                from ledger import LEDGER

                class Owner:
                    def __del__(self):
                        LEDGER.drop("x")
            """}, select=["TL012"])
        assert rules_of(fs) == ["TL012"]
        assert fs[0].path.endswith("ledger.py")

    def test_lock_free_deferral_is_clean(self, tmp_path):
        # the drop_deferred pattern: finalizers append to a deque, the
        # locked retirement happens on a normal thread later
        fs = lint(tmp_path, """
            import threading
            from collections import deque

            class Ledger:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._entries = {}
                    self._deferred = deque()

                def drop(self, key):
                    with self._lock:
                        self._entries.pop(key, None)

                def drop_deferred(self, key):
                    self._deferred.append(key)

            class Owner:
                def __init__(self, ledger, key):
                    self._ledger = ledger
                    self.key = key

                def release(self):
                    pass

                def __del__(self):
                    self.release()
        """, select=["TL012"])
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Ring:
                def __init__(self):
                    self._lock = threading.RLock()
                    self._items = []

                def close(self):
                    # tracelint: disable=TL012 -- fixture: RLock, short sections
                    with self._lock:
                        self._items.clear()

                def __del__(self):
                    self.close()
        """, select=["TL012"])
        assert fs == []


# ------------------------------------------------------------------ #
# TL013 — callback invoked under a held lock
# ------------------------------------------------------------------ #

class TestTL013CallbackUnderLock:
    def test_on_token_under_condition(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Stream:
                def __init__(self, on_token):
                    self._cv = threading.Condition()
                    self._toks = []
                    self._on_token = on_token

                def push(self, tok):
                    with self._cv:
                        self._toks.append(tok)
                        self._on_token(0, tok)
        """, select=["TL013"])
        assert rules_of(fs) == ["TL013"]
        assert "_on_token" in fs[0].message
        assert "Stream._cv" in fs[0].message

    def test_param_callback_under_module_lock(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            _lock = threading.Lock()
            _subs = []

            def register(callback):
                with _lock:
                    _subs.append(callback)
                    callback(len(_subs))
        """, select=["TL013"])
        assert rules_of(fs) == ["TL013"]

    def test_callback_outside_lock_is_clean(self, tmp_path):
        # the _push-outside-_lock discipline: append under the lock,
        # fire the callback after releasing it
        fs = lint(tmp_path, """
            import threading

            class Stream:
                def __init__(self, on_token):
                    self._cv = threading.Condition()
                    self._toks = []
                    self._on_token = on_token

                def push(self, tok):
                    with self._cv:
                        self._toks.append(tok)
                        self._cv.notify_all()
                    if self._on_token is not None:
                        self._on_token(0, tok)
        """, select=["TL013"])
        assert fs == []

    def test_project_internal_hook_method_is_clean(self, tmp_path):
        # a name that matches the callback vocabulary but resolves to a
        # method of the project is internal, not user-supplied
        fs = lint(tmp_path, """
            import threading

            class Prof:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._rows = []

                def _flush_hook(self):
                    pass

                def record(self, row):
                    with self._lock:
                        self._rows.append(row)
                        self._flush_hook()
        """, select=["TL013"])
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            _lock = threading.Lock()

            def register(callback):
                with _lock:
                    # tracelint: disable=TL013 -- fixture: callback is doc'd lock-free
                    callback(1)
        """, select=["TL013"])
        assert fs == []


# ------------------------------------------------------------------ #
# TL014 — thread lifecycle
# ------------------------------------------------------------------ #

class TestTL014ThreadLifecycle:
    def test_non_daemon_unjoined_class_thread(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Worker:
                def start(self):
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()

                def _run(self):
                    pass
        """, select=["TL014"])
        assert rules_of(fs) == ["TL014"]
        assert "daemon" in fs[0].message and "join" in fs[0].message

    def test_daemon_thread_is_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Worker:
                def start(self):
                    self._thread = threading.Thread(target=self._run,
                                                    daemon=True)
                    self._thread.start()

                def _run(self):
                    pass
        """, select=["TL014"])
        assert fs == []

    def test_joined_on_close_is_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Worker:
                def start(self):
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()

                def _run(self):
                    pass

                def close(self):
                    self._thread.join(timeout=5)
        """, select=["TL014"])
        assert fs == []

    def test_blocking_get_without_pill(self, tmp_path):
        fs = lint(tmp_path, """
            import queue
            import threading

            class Ring:
                def __init__(self):
                    self._q = queue.Queue()
                    self._thread = threading.Thread(
                        target=self._produce, daemon=True)
                    self._thread.start()

                def _produce(self):
                    self._q.put(1)

                def take(self):
                    return self._q.get()
        """, select=["TL014"])
        assert rules_of(fs) == ["TL014"]
        assert "poison-pill" in fs[0].message

    def test_sentinel_pill_on_close_is_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import queue
            import threading

            _END = object()

            class Ring:
                def __init__(self):
                    self._q = queue.Queue()
                    self._thread = threading.Thread(
                        target=self._produce, daemon=True)
                    self._thread.start()

                def _produce(self):
                    self._q.put(1)

                def take(self):
                    return self._q.get()

                def close(self):
                    self._q.put_nowait(_END)
        """, select=["TL014"])
        assert fs == []

    def test_bounded_get_is_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import queue
            import threading

            class Ring:
                def __init__(self):
                    self._q = queue.Queue()
                    self._thread = threading.Thread(
                        target=self._produce, daemon=True)
                    self._thread.start()

                def _produce(self):
                    self._q.put(1)

                def take(self):
                    return self._q.get(timeout=0.2)
        """, select=["TL014"])
        assert fs == []

    def test_positional_timeout_get_is_bounded(self, tmp_path):
        # review regression: get(True, 1.0) has a positional timeout
        # and wakes on its own — not an unbounded blocking get
        fs = lint(tmp_path, """
            import queue
            import threading

            class Ring:
                def __init__(self):
                    self._q = queue.Queue()
                    self._thread = threading.Thread(
                        target=self._produce, daemon=True)
                    self._thread.start()

                def _produce(self):
                    self._q.put(1)

                def take(self):
                    return self._q.get(True, 1.0)
        """, select=["TL014"])
        assert fs == []

    def test_thread_stored_into_pool_and_joined_is_clean(self, tmp_path):
        # review regression: a local handle appended to a worker pool
        # (and joined from it on teardown) has transferred ownership
        fs = lint(tmp_path, """
            import threading

            class Pool:
                def __init__(self):
                    self._workers = []

                def spawn(self, fn):
                    t = threading.Thread(target=fn)
                    t.start()
                    self._workers.append(t)

                def close(self):
                    for t in self._workers:
                        t.join()
        """, select=["TL014"])
        assert fs == []

    def test_local_thread_returned_transfers_ownership(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            def spawn(fn):
                t = threading.Thread(target=fn)
                t.start()
                return t

            def fire_and_forget(fn):
                t = threading.Thread(target=fn)
                t.start()
        """, select=["TL014"])
        assert rules_of(fs) == ["TL014"]
        assert "fire_and_forget" in fs[0].message

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import threading

            class Worker:
                def start(self):
                    # tracelint: disable=TL014 -- fixture: joined by the owner
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()

                def _run(self):
                    pass
        """, select=["TL014"])
        assert fs == []


# ------------------------------------------------------------------ #
# TL015 — telemetry schema / fault-site contract
# ------------------------------------------------------------------ #

def _tele_docs(tmp_path, kinds=(), metrics=()):
    d = tmp_path / "docs"
    d.mkdir(exist_ok=True)
    f = d / "TELEMETRY.md"
    lines = ["## Event log", "", "### Event schema", "",
             "| kind | fields |", "|---|---|"]
    lines += [f"| `{k}` | stuff |" for k in kinds]
    lines += ["", "## Metrics schema", "", "| name | kind |", "|---|---|"]
    lines += [f"| `{m}` | counter |" for m in metrics]
    f.write_text("\n".join(lines) + "\n")
    return str(f)


def _fault_docs(tmp_path, sites):
    d = tmp_path / "docs"
    d.mkdir(exist_ok=True)
    f = d / "ENV_VARS.md"
    site_s = " / ".join(f"`{s}`" for s in sites)
    f.write_text(
        "| Variable | Default | Effect |\n|---|---|---|\n"
        f"| `MXNET_FAULT_INJECT` | unset | rules. Sites: {site_s}. "
        "Kinds: `raise` (`os.kill` for kill). |\n")
    return str(f)


class TestTL015TelemetryContract:
    def test_documented_kinds_and_metrics_are_clean(self, tmp_path):
        docs = _tele_docs(tmp_path, kinds=("boot",),
                          metrics=("requests_total",))
        fs = lint(tmp_path, """
            from mxnet_tpu import telemetry

            def up():
                telemetry.emit("boot", ok=1)
                telemetry.counter("requests_total").inc()
        """, select=["TL015"], telemetry_docs=docs)
        assert fs == []

    def test_event_drift_is_bidirectional(self, tmp_path):
        # ISSUE acceptance: an emitted-but-undocumented kind fails AND
        # a documented-but-never-emitted kind fails
        docs = _tele_docs(tmp_path, kinds=("boot", "ghost"))
        fs = lint(tmp_path, """
            from mxnet_tpu import telemetry

            def up():
                telemetry.emit("boot")
                telemetry.emit("rogue", oops=1)
        """, select=["TL015"], telemetry_docs=docs)
        assert rules_of(fs) == ["TL015", "TL015"]
        msgs = {f.message for f in fs}
        assert any("`rogue`" in m and "emitted here" in m for m in msgs)
        assert any("`ghost`" in m and "never" in m for m in msgs)
        doc_hit = [f for f in fs if "`ghost`" in f.message]
        assert doc_hit[0].path.endswith("TELEMETRY.md")

    def test_metric_drift_is_bidirectional(self, tmp_path):
        docs = _tele_docs(tmp_path, metrics=("good_total", "ghost_total"))
        fs = lint(tmp_path, """
            from mxnet_tpu import telemetry

            def up():
                telemetry.counter("good_total").inc()
                telemetry.gauge("rogue_depth").set(1)
        """, select=["TL015"], telemetry_docs=docs)
        msgs = " ".join(f.message for f in fs)
        assert "`rogue_depth`" in msgs and "`ghost_total`" in msgs

    def test_fstring_metric_family_covers_doc_rows(self, tmp_path):
        # the _CounterView shape: f"serve_{k}_total" covers the
        # concrete documented family names in the stale direction
        docs = _tele_docs(tmp_path,
                          metrics=("serve_step_dispatches_total",))
        fs = lint(tmp_path, """
            from mxnet_tpu import telemetry

            def make(k):
                return telemetry.counter(f"serve_{k}_total", server="s")
        """, select=["TL015"], telemetry_docs=docs)
        assert fs == []

    def test_emit_forwarder_wrapper_counts(self, tmp_path):
        # tools/launch.py's _emit(kind, **fields) wrapper: a literal
        # through the forwarder is an emit of that kind
        docs = _tele_docs(tmp_path, kinds=("boot",))
        fs = lint(tmp_path, """
            from mxnet_tpu import telemetry

            def _emit(kind, **fields):
                telemetry.emit(kind, **fields)

            def up():
                _emit("rogue", rank=0)
                _emit("boot")
        """, select=["TL015"], telemetry_docs=docs)
        assert rules_of(fs) == ["TL015"]
        assert "`rogue`" in fs[0].message

    def test_fault_site_drift_is_bidirectional(self, tmp_path):
        docs = _fault_docs(tmp_path, ["serve.pump", "serve.ghost"])
        fs = lint(tmp_path, """
            from mxnet_tpu.telemetry.faults import fault_point

            def pump():
                fault_point("serve.pump")
                fault_point("serve.mystery")
        """, select=["TL015"], env_docs=docs)
        msgs = " ".join(f.message for f in fs)
        assert "`serve.mystery`" in msgs and "`serve.ghost`" in msgs
        # the Kinds: tail ('os.kill') must not count as a site
        assert "os.kill" not in msgs

    def test_suppressed(self, tmp_path):
        docs = _tele_docs(tmp_path, kinds=("boot",))
        fs = lint(tmp_path, """
            from mxnet_tpu import telemetry

            def up():
                telemetry.emit("boot")
                # tracelint: disable=TL015 -- fixture: internal debug-only kind
                telemetry.emit("rogue")
        """, select=["TL015"], telemetry_docs=docs)
        assert fs == []

    def test_repo_parity_gate(self):
        """The TL015 self-check mirror of the TL005 gate: code event
        kinds / metric names / fault sites and the docs tables agree,
        both directions, over the full lint target."""
        r = cli(["mxnet_tpu/", "tools/", "benchmark/", "--select",
                 "TL015", "--format=json"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["findings"] == []

    def test_external_env_docs_does_not_blind_telemetry_scan(
            self, tmp_path):
        """Review regression: an --env-docs override outside the repo
        must not re-root the TELEMETRY.md stale-direction scan — each
        docs file is reconciled against the tree that owns it."""
        d = tmp_path / "docs"
        d.mkdir()
        (d / "ENV_VARS.md").write_text(
            "| Variable | Default | Effect |\n|---|---|---|\n")
        r = cli(["mxnet_tpu/telemetry/faults.py", "--env-docs",
                 str(d / "ENV_VARS.md"), "--select", "TL015",
                 "--format=json"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["findings"] == []


# ------------------------------------------------------------------ #
# TL016–TL019 — the executable-contract family (tracelint v4) over a
# miniature operand-schema registry mirroring serve/schema.py's shape
# ------------------------------------------------------------------ #

_SCHEMA_FIXTURE = """
    EXECUTABLES = {
        "admit": {
            "module": "engine",
            "getter": "admit_fn",
            "operands": ("params", "prompts", "meta", "pages",
                         "kp", "vp", "pos", "tok", "active"),
            "donated": ("kp", "vp"),
        },
    }
    SLOT_STATE = (
        ("pos", "int32", 1),
        ("tok", "int32", 1),
        ("active", "bool", 1),
    )
"""


class TestTL016DonationDrift:
    def test_stale_literal_positions(self, tmp_path):
        """Literal donate indices that disagree with the registry's
        donated positions — the producer half of the PR-18 class."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                import jax

                def admit(params, prompts, meta, pages,
                          kp, vp, pos, tok, active):
                    return (kp, vp, pos, tok, active)

                fn = jax.jit(admit, donate_argnums=(5, 6))
            """}, select=["TL016"])
        assert rules_of(fs) == ["TL016"]
        assert "disagree with the operand schema" in fs[0].message
        assert fs[0].severity == "error"

    def test_inserted_operand_without_donate_shift(self, tmp_path):
        """The exact PR-18 recycled-page shape: a new operand lands in
        the signature, the literal donation pair does not move, and the
        'right' indices now donate the wrong buffers."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                import jax

                def admit(params, prompts, extra, meta, pages,
                          kp, vp, pos, tok, active):
                    return (kp, vp, pos, tok, active)

                fn = jax.jit(admit, donate_argnums=(4, 5))
            """}, select=["TL016"])
        assert rules_of(fs) == ["TL016"]
        assert "PR-18" in fs[0].message
        assert "'pages'" in fs[0].message

    def test_jit_donate_derivation_is_clean(self, tmp_path):
        """Deriving the indices from the registry is the sanctioned
        pattern — the runtime validates the signature at build time."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                import jax
                import schema

                def admit(params, prompts, meta, pages,
                          kp, vp, pos, tok, active):
                    return (kp, vp, pos, tok, active)

                fn = jax.jit(admit,
                             donate_argnums=schema.jit_donate(
                                 "admit", admit))
            """}, select=["TL016"])
        assert fs == []

    def test_matching_literal_is_clean(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                import jax

                def admit(params, prompts, meta, pages,
                          kp, vp, pos, tok, active):
                    return (kp, vp, pos, tok, active)

                fn = jax.jit(admit, donate_argnums=(4, 5))
            """}, select=["TL016"])
        assert fs == []

    def test_non_registry_index_past_arity(self, tmp_path):
        """Outside the registry the producer-side TL002 generalization:
        a donation index past the wrapped function's positional arity
        donates a buffer that does not exist."""
        fs = lint(tmp_path, """
            import jax

            def step(w, g):
                return w - g

            fn = jax.jit(step, donate_argnums=(2,))
        """, select=["TL016"])
        assert rules_of(fs) == ["TL016"]
        assert "exceed" in fs[0].message

    def test_suppressed(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                import jax

                def admit(params, prompts, meta, pages,
                          kp, vp, pos, tok, active):
                    return (kp, vp, pos, tok, active)

                # tracelint: disable=TL016 -- fixture: transitional donation map
                fn = jax.jit(admit, donate_argnums=(5, 6))
            """}, select=["TL016"])
        assert fs == []


class TestTL017SlotStateLayout:
    def test_hard_coded_meta_column(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                def admit(params, prompts, meta, pages,
                          kp, vp, pos, tok, active):
                    valid = meta[:, 0]
                    return (kp, vp, pos, tok, active)
            """}, select=["TL017"])
        assert rules_of(fs) == ["TL017"]
        assert "meta column index 0" in fs[0].message

    def test_dispatch_side_meta_builder_flagged(self, tmp_path):
        """A module that fetches executables through registry getters
        builds the rows those bodies unpack — its hand-numbered writes
        drift the same way."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "server.py": """
                class Srv:
                    def push(self, meta):
                        fn = self.progs.admit_fn(4)
                        meta[:, 1] = 0
                        return fn
            """}, select=["TL017"])
        assert rules_of(fs) == ["TL017"]

    def test_state_tuple_arity_drift(self, tmp_path):
        """A column threaded through some scatter sites but not the
        schema: the tuple's arity disagrees with kp, vp + SLOT_STATE."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                def admit(params, prompts, meta, pages,
                          kp, vp, pos, tok, active):
                    ttl = pos
                    return (kp, vp, pos, tok, active, ttl)
            """}, select=["TL017"])
        assert rules_of(fs) == ["TL017"]
        assert "6 elements" in fs[0].message
        assert "declares 5" in fs[0].message

    def test_literal_byte_total(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                _SLOT_STATE_BYTES = 9
            """}, select=["TL017"])
        assert rules_of(fs) == ["TL017"]
        assert "slot_state_bytes()" in fs[0].message

    def test_schema_indexing_is_clean(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                import schema

                _SLOT_STATE_BYTES = schema.slot_state_bytes()
                _AM = schema.meta_cols("admit")

                def admit(params, prompts, meta, pages,
                          kp, vp, pos, tok, active):
                    valid = meta[:, _AM["valid"]]
                    return (kp, vp, pos, tok, active)
            """}, select=["TL017"])
        assert fs == []

    def test_meta_outside_contract_scope_is_clean(self, tmp_path):
        """A module that neither defines executables nor dispatches
        them can call its locals whatever it likes."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "report.py": """
                def summarize(meta):
                    return meta[:, 0].sum()
            """}, select=["TL017"])
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "engine.py": """
                def admit(params, prompts, meta, pages,
                          kp, vp, pos, tok, active):
                    # tracelint: disable=TL017 -- fixture: migration shim, schema lands next PR
                    valid = meta[:, 0]
                    return (kp, vp, pos, tok, active)
            """}, select=["TL017"])
        assert fs == []


class TestTL018DispatchArity:
    def test_missing_operand_in_dispatch(self, tmp_path):
        """The 'zpages lands in 2 of 3 admission paths' class: one
        dispatch site passes one operand fewer than declared."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "server.py": """
                class Srv:
                    def pump(self):
                        fn = self.progs.admit_fn(4)
                        return fn(self.params, self.prompts, self.meta,
                                  *self._state)
            """}, select=["TL018"])
        assert rules_of(fs) == ["TL018"]
        assert "passes 8" in fs[0].message
        assert "declares 9" in fs[0].message
        assert "params, prompts, meta" in fs[0].message  # operand list

    def test_exact_arity_is_clean(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "server.py": """
                class Srv:
                    def pump(self):
                        fn = self.progs.admit_fn(4)
                        return fn(self.params, self.prompts, self.meta,
                                  self.pages, *self._state)
            """}, select=["TL018"])
        assert fs == []

    def test_immediate_getter_call_counted(self, tmp_path):
        """fn-less dispatch — getter(...)(operands...) — is the same
        call-site."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "server.py": """
                class Srv:
                    def pump(self):
                        return self.progs.admit_fn(4)(
                            self.params, self.meta, self.pages,
                            *self._state)
            """}, select=["TL018"])
        assert rules_of(fs) == ["TL018"]

    def test_uncountable_splat_is_skipped(self, tmp_path):
        """A non-state splat hides the operand count — not this rule's
        call to make."""
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "server.py": """
                class Srv:
                    def pump(self, argpack):
                        fn = self.progs.admit_fn(4)
                        return fn(*argpack)
            """}, select=["TL018"])
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint_tree(tmp_path, {
            "schema.py": _SCHEMA_FIXTURE,
            "server.py": """
                class Srv:
                    def pump(self):
                        fn = self.progs.admit_fn(4)
                        # tracelint: disable=TL018 -- fixture: legacy replay path, operand added downstream
                        return fn(self.params, self.prompts, self.meta,
                                  *self._state)
            """}, select=["TL018"])
        assert fs == []


class TestTL019PlacementDiscipline:
    def test_local_devices_chain_into_sharding(self, tmp_path):
        """The elastic-resume hazard: a host-local device list flows
        through mesh and sharding construction into device_put — every
        link in the chain is flagged."""
        fs = lint(tmp_path, """
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            def build(x):
                devs = jax.local_devices()
                mesh = Mesh(devs, ("dp",))
                sh = NamedSharding(mesh, P("dp"))
                return jax.device_put(x, sh)
        """, select=["TL019"])
        assert rules_of(fs) == ["TL019", "TL019", "TL019"]
        assert all("jax.local_devices()" in f.message for f in fs)
        assert len({f.line for f in fs}) == 3

    def test_env_read_into_partition_spec(self, tmp_path):
        fs = lint(tmp_path, """
            import os
            from jax.sharding import PartitionSpec

            def spec():
                axis = os.environ["RANK_AXIS"]
                return PartitionSpec(axis)
        """, select=["TL019"])
        assert rules_of(fs) == ["TL019"]
        assert "os.environ" in fs[0].message

    def test_pod_global_devices_are_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            def build(x):
                devs = jax.devices()
                mesh = Mesh(devs, ("dp",))
                sh = NamedSharding(mesh, P("dp"))
                return jax.device_put(x, sh)
        """, select=["TL019"])
        assert fs == []

    def test_mesh_helper_definitions_exempt(self, tmp_path):
        """The parallel.mesh helpers ARE the sanctioned boundary —
        their internals legitimately touch process locality."""
        fs = lint(tmp_path, """
            import jax
            from jax.sharding import Mesh

            def make_mesh(axes):
                devs = jax.local_devices()
                return Mesh(devs, tuple(axes))

            def global_put(x, sharding):
                rank = jax.process_index()
                return jax.make_array_from_process_local_data(
                    sharding, x)
        """, select=["TL019"])
        assert fs == []

    def test_helper_output_is_clean(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            from mxnet_tpu.parallel.mesh import data_sharding

            def put(x):
                sh = data_sharding()
                return jax.device_put(x, sh)
        """, select=["TL019"])
        assert fs == []

    def test_suppressed(self, tmp_path):
        fs = lint(tmp_path, """
            import jax
            from jax.sharding import Mesh

            def build():
                devs = jax.local_devices()
                # tracelint: disable=TL019 -- fixture: single-host tool, never runs on a pod
                return Mesh(devs, ("dp",))
        """, select=["TL019"])
        assert fs == []


# ------------------------------------------------------------------ #
# seeded historical bugs (ISSUE 14 acceptance): each of the three
# hand-caught PR-7/10/13 bug classes must fail on a mutation of the
# REAL runtime code and stay clean on HEAD
# ------------------------------------------------------------------ #

class TestSeededHistoricalBugs:
    def test_seeded_wall_clock_deadline_fails_gate(self, tmp_path):
        """The PR-13 bug class: serve close()'s drain deadline computed
        on the wall clock instead of time.monotonic() (TL011)."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "serve", "server.py")).read()
        needle = "        deadline = time.monotonic() + timeout\n"
        assert needle in src
        clean = tmp_path / "server_head.py"
        clean.write_text(src)
        r = cli([str(clean), "--select", "TL011", "--format=json"])
        assert r.returncode == 0, r.stdout   # HEAD is clean
        seeded = src.replace(
            needle, "        deadline = time.time() + timeout\n", 1)
        bad = tmp_path / "server_seeded.py"
        bad.write_text(seeded)
        r = cli([str(bad), "--select", "TL011", "--format=json"])
        assert r.returncode == 1
        hits = json.loads(r.stdout)["findings"]
        assert any(f["rule"] == "TL011" and "monotonic" in f["message"]
                   for f in hits)

    def _mirror(self, tmp_path, trainer_src):
        """Rebuild the trainer/memory package seam under tmp so the
        cross-module singleton resolution works like in the repo."""
        for rel in ("mxnet_tpu/__init__.py",
                    "mxnet_tpu/gluon/__init__.py",
                    "mxnet_tpu/telemetry/__init__.py"):
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text("")
        (tmp_path / "mxnet_tpu" / "telemetry" / "memory.py").write_text(
            open(os.path.join(REPO, "mxnet_tpu", "telemetry",
                              "memory.py")).read())
        (tmp_path / "mxnet_tpu" / "gluon" / "trainer.py").write_text(
            trainer_src)

    def test_seeded_finalizer_accountant_lock_fails_gate(self, tmp_path):
        """The PR-10 bug class: Trainer's GC finalizer taking the
        process-wide accountant lock instead of the lock-free
        drop_deferred path (TL012, resolved through the ACCOUNTANT
        singleton two modules away)."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "gluon", "trainer.py")).read()
        needle = 'ACCOUNTANT.drop_deferred("train.params",'
        assert needle in src
        self._mirror(tmp_path, src)
        r = cli([str(tmp_path), "--select", "TL012", "--format=json"])
        assert r.returncode == 0, r.stdout   # HEAD is clean
        self._mirror(tmp_path, src.replace(
            needle, 'ACCOUNTANT.drop("train.params",', 1))
        r = cli([str(tmp_path), "--select", "TL012", "--format=json"])
        assert r.returncode == 1
        hits = json.loads(r.stdout)["findings"]
        assert any(f["rule"] == "TL012" and "__del__" in f["message"]
                   and f["path"].endswith("memory.py") for f in hits)

    def test_seeded_on_token_under_lock_fails_gate(self, tmp_path):
        """The PR-7 bug class: the per-token user callback invoked
        inside the stream's condition instead of after releasing it
        (TL013)."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "serve", "server.py")).read()
        needle = ("        with self._cv:\n"
                  "            self._toks.append(tok)\n"
                  "            self._cv.notify_all()\n")
        assert needle in src
        clean = tmp_path / "server_head.py"
        clean.write_text(src)
        r = cli([str(clean), "--select", "TL013", "--format=json"])
        assert r.returncode == 0, r.stdout   # HEAD is clean
        seeded = src.replace(needle, (
            "        with self._cv:\n"
            "            self._toks.append(tok)\n"
            "            if self._on_token is not None:\n"
            "                self._on_token(self.request_id, tok)\n"
            "            self._cv.notify_all()\n"), 1)
        bad = tmp_path / "server_seeded.py"
        bad.write_text(seeded)
        r = cli([str(bad), "--select", "TL013", "--format=json"])
        assert r.returncode == 1
        hits = json.loads(r.stdout)["findings"]
        assert any(f["rule"] == "TL013" and "_on_token" in f["message"]
                   for f in hits)


# ------------------------------------------------------------------ #
# seeded contract drift (ISSUE 20 acceptance): mutations reproducing
# the PR-18 recycled-page drift shape against the REAL serve engine/
# server must fail at error level while the HEAD copies lint clean
# ------------------------------------------------------------------ #

class TestSeededContractDrift:
    def _mirror(self, tmp_path, name, src):
        """The registry module plus one consumer, side by side — the
        linter reads EXECUTABLES/SLOT_STATE straight out of the AST,
        so no package scaffolding is needed."""
        (tmp_path / "schema.py").write_text(open(os.path.join(
            REPO, "mxnet_tpu", "serve", "schema.py")).read())
        (tmp_path / name).write_text(src)

    def test_head_engine_and_server_are_clean(self, tmp_path):
        for name in ("engine.py", "server.py"):
            src = open(os.path.join(
                REPO, "mxnet_tpu", "serve", name)).read()
            self._mirror(tmp_path, name, src)
        r = cli([str(tmp_path), "--select", "TL016,TL017,TL018",
                 "--format=json"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["findings"] == []

    def test_seeded_admit_operand_without_donate_shift(self, tmp_path):
        """THE PR-18 shape: an operand inserted into admit's signature
        while a literal donation pair stays put — positions 6/7 now
        name zpages/kp and the wrong buffer dies silently (TL016)."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "serve", "engine.py")).read()
        sig = ("def admit(param_vals, prompts, meta, dls, pages, "
               "zpages, kp, vp,")
        don = 'donate_argnums=schema.jit_donate("admit", admit)),'
        assert sig in src and don in src
        seeded = src.replace(
            sig, "def admit(param_vals, prompts, scratch_rows, meta, "
                 "dls, pages, zpages, kp, vp,", 1
        ).replace(don, "donate_argnums=(6, 7)),", 1)
        self._mirror(tmp_path, "engine.py", seeded)
        r = cli([str(tmp_path), "--select", "TL016", "--format=json"])
        assert r.returncode == 1
        hits = json.loads(r.stdout)["findings"]
        assert any(f["rule"] == "TL016" and "PR-18" in f["message"]
                   and f["severity"] == "error" for f in hits)

    def test_seeded_state_column_through_three_sites(self, tmp_path):
        """A tenth slot-state column threaded through the three
        new-state construction sites but not the schema: every drifted
        tuple is flagged (TL017)."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "serve", "engine.py")).read()
        needle = "(kp, vp, pos, tok, active, stop, keys, dl, spec)"
        assert src.count(needle) == 3
        seeded = src.replace(
            needle, "(kp, vp, pos, tok, active, stop, keys, dl, spec, "
                    "ttl)")
        self._mirror(tmp_path, "engine.py", seeded)
        r = cli([str(tmp_path), "--select", "TL017", "--format=json"])
        assert r.returncode == 1
        hits = [f for f in json.loads(r.stdout)["findings"]
                if f["rule"] == "TL017"]
        assert len(hits) == 3
        assert all("10 elements" in f["message"] and
                   "declares 9" in f["message"] for f in hits)

    def test_seeded_literal_byte_total(self, tmp_path):
        """Hard-coding the 29 back in place of the schema-priced total
        is flagged (TL017) — the ledger must not drift from the
        layout."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "serve", "engine.py")).read()
        needle = "_SLOT_STATE_BYTES = schema.slot_state_bytes()"
        assert needle in src
        seeded = src.replace(needle, "_SLOT_STATE_BYTES = 29", 1)
        self._mirror(tmp_path, "engine.py", seeded)
        r = cli([str(tmp_path), "--select", "TL017", "--format=json"])
        assert r.returncode == 1
        hits = json.loads(r.stdout)["findings"]
        assert any(f["rule"] == "TL017" and
                   "slot_state_bytes()" in f["message"] for f in hits)

    def test_seeded_dispatch_drops_zpages(self, tmp_path):
        """The 'zpages lands in 2 of 3 admission paths' class: the COW
        admission dispatch loses an operand (TL018)."""
        src = open(os.path.join(
            REPO, "mxnet_tpu", "serve", "server.py")).read()
        needle = "fn(meta, dls, srcs, dsts, zpages, *self._state)"
        assert needle in src
        seeded = src.replace(
            needle, "fn(meta, dls, srcs, dsts, *self._state)", 1)
        self._mirror(tmp_path, "server.py", seeded)
        r = cli([str(tmp_path), "--select", "TL018", "--format=json"])
        assert r.returncode == 1
        hits = json.loads(r.stdout)["findings"]
        assert any(f["rule"] == "TL018" and "passes 13" in f["message"]
                   and "declares 14" in f["message"] for f in hits)


# ------------------------------------------------------------------ #
# SARIF output
# ------------------------------------------------------------------ #

class TestSarif:
    BAD = """
        import jax

        def step(w, g):
            lr = float(g)
            return w - lr * g

        fn = jax.jit(step)
    """

    def test_minimal_sarif_2_1_0_shape(self, tmp_path):
        """The SARIF 2.1.0 minimal-schema shape pin: version, tool
        driver with a rule table, results with ruleId/level/message/
        physical locations."""
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(self.BAD))
        r = cli([str(bad), "--format", "sarif"])
        assert r.returncode == 1
        doc = json.loads(r.stdout)
        assert doc["version"] == "2.1.0"
        assert doc["$schema"].endswith("sarif-2.1.0.json")
        run = doc["runs"][0]
        driver = run["tool"]["driver"]
        assert driver["name"] == "tracelint"
        rule_ids = {rl["id"] for rl in driver["rules"]}
        assert {"TL001", "TL011", "TL015"} <= rule_ids
        res = run["results"][0]
        assert res["ruleId"] == "TL001"
        assert res["level"] == "error"
        assert "float" in res["message"]["text"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("bad.py")
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1

    def test_clean_run_has_empty_results(self, tmp_path):
        (tmp_path / "ok.py").write_text("x = 1\n")
        r = cli([str(tmp_path), "--format", "sarif"])
        assert r.returncode == 0
        assert json.loads(r.stdout)["runs"][0]["results"] == []

    def test_warn_severity_maps_to_warning_level(self, tmp_path):
        (tmp_path / "warny.py").write_text(textwrap.dedent("""
            from jax import lax

            def ring_pass(x, axis="sp"):
                return lax.ppermute(x, axis_name=axis, perm=[])

            def fold(x):
                return lax.psum(x, "sp")
        """))
        r = cli([str(tmp_path), "--format", "sarif"])
        assert r.returncode == 0   # warnings don't fail the gate
        res = json.loads(r.stdout)["runs"][0]["results"]
        assert res and res[0]["level"] == "warning"

    def test_v4_contract_rules_in_driver_and_results(self, tmp_path):
        """The v4 rule table rides the same sorted(RULES) rendering:
        TL016–TL019 appear in the driver and fire at error level."""
        for name, source in {
                "schema.py": _SCHEMA_FIXTURE,
                "engine.py": """
                    import jax

                    def admit(params, prompts, meta, pages,
                              kp, vp, pos, tok, active):
                        return (kp, vp, pos, tok, active)

                    fn = jax.jit(admit, donate_argnums=(5, 6))
                """}.items():
            (tmp_path / name).write_text(textwrap.dedent(source))
        r = cli([str(tmp_path), "--select", "TL016", "--format",
                 "sarif"])
        assert r.returncode == 1
        run = json.loads(r.stdout)["runs"][0]
        rule_ids = {rl["id"] for rl in run["tool"]["driver"]["rules"]}
        assert {"TL016", "TL017", "TL018", "TL019"} <= rule_ids
        res = run["results"][0]
        assert res["ruleId"] == "TL016"
        assert res["level"] == "error"


# ------------------------------------------------------------------ #
# --jobs — parallel lint determinism (all three formats)
# ------------------------------------------------------------------ #

class TestJobs:
    def _tree(self, tmp_path):
        for i in range(3):
            (tmp_path / f"mod{i}.py").write_text(textwrap.dedent(f"""
                import jax

                def step{i}(w, g):
                    lr = float(g)
                    return w - lr * g

                fn{i} = jax.jit(step{i})
            """))

    def test_parallel_output_identical_to_serial(self, tmp_path):
        self._tree(tmp_path)
        for fmt in ("text", "json", "sarif"):
            serial = cli([str(tmp_path), f"--format={fmt}"])
            parallel = cli([str(tmp_path), f"--format={fmt}",
                            "--jobs", "3"])
            assert serial.returncode == parallel.returncode == 1, fmt
            assert serial.stdout == parallel.stdout, fmt

    def test_jobs_accepted_on_clean_tree(self, tmp_path):
        (tmp_path / "ok.py").write_text("x = 1\n")
        r = cli([str(tmp_path), "--jobs", "2"])
        assert r.returncode == 0, r.stdout


# ------------------------------------------------------------------ #
# --changed-only — the pre-commit fast path: report scoped to the
# git-changed set, byte-identical to a full run filtered to it
# ------------------------------------------------------------------ #

class TestChangedOnly:
    BAD = """
        import jax

        def step{i}(w, g):
            lr = float(g)
            return w - lr * g

        fn{i} = jax.jit(step{i})
    """

    def _git(self, cwd, *args):
        subprocess.run(
            ["git", "-c", "user.name=t", "-c", "user.email=t@t",
             *args],
            cwd=str(cwd), check=True, capture_output=True, env=_ENV)

    def _seed_repo(self, tmp_path):
        for i in range(2):
            (tmp_path / f"mod{i}.py").write_text(
                textwrap.dedent(self.BAD.format(i=i)))
        self._git(tmp_path, "init", "-q")
        self._git(tmp_path, "add", ".")
        self._git(tmp_path, "commit", "-qm", "seed")

    def _cli(self, cwd, args):
        # run from inside the throwaway checkout; the package resolves
        # off PYTHONPATH so --changed-only scopes to THAT repo's diff
        return subprocess.run(
            [sys.executable, "-m", "tools.tracelint"] + args,
            capture_output=True, text=True, cwd=str(cwd),
            env=dict(_ENV, PYTHONPATH=REPO))

    def test_byte_identical_to_filtered_full_run(self, tmp_path):
        self._seed_repo(tmp_path)
        p = tmp_path / "mod1.py"
        p.write_text(p.read_text() + "\n# touched\n")
        full = self._cli(tmp_path, [".", "--format=json"])
        changed = self._cli(tmp_path, [".", "--changed-only",
                                       "--format=json"])
        assert full.returncode == changed.returncode == 1
        want = [f for f in json.loads(full.stdout)["findings"]
                if f["path"].endswith("mod1.py")]
        got = json.loads(changed.stdout)["findings"]
        assert want and got == want

    def test_clean_changed_file_passes_despite_dirty_neighbors(
            self, tmp_path):
        """Only the changed set is REPORTED — committed findings in
        untouched modules don't block the pre-commit run."""
        self._seed_repo(tmp_path)
        (tmp_path / "newmod.py").write_text("x = 1\n")   # untracked
        r = self._cli(tmp_path, [".", "--changed-only",
                                 "--format=json"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["findings"] == []

    def test_no_changes_is_clean(self, tmp_path):
        self._seed_repo(tmp_path)
        r = self._cli(tmp_path, [".", "--changed-only",
                                 "--format=json"])
        assert r.returncode == 0, r.stdout
        assert json.loads(r.stdout)["findings"] == []

    def test_outside_git_checkout_is_usage_error(self, tmp_path):
        (tmp_path / "ok.py").write_text("x = 1\n")
        r = self._cli(tmp_path, [".", "--changed-only"])
        assert r.returncode == 2
        assert "git" in r.stderr


# ------------------------------------------------------------------ #
# perf: the shared lock analysis must keep the serial full-target run
# near the PR-11 mark (loose wall-clock ceiling, not a microbenchmark)
# ------------------------------------------------------------------ #

class TestSerialRunBudget:
    def test_full_target_serial_run_stays_fast(self):
        import time as _time

        t0 = _time.monotonic()
        run_paths([os.path.join(REPO, p)
                   for p in ("mxnet_tpu", "tools", "benchmark")])
        dt = _time.monotonic() - t0
        # PR-11 anchored ~9s; the v3 rules ride the shared lock/aux
        # analyses, so even a slow CI container stays well under this
        assert dt < 30.0, f"serial tracelint run took {dt:.1f}s"
