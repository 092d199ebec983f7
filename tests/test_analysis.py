"""The JAX-aware lint pass (``repro.analysis``): rules, suppressions,
baseline ratchet, and the repo-wide dogfood gate.

Rule tests run the real driver over synthetic fixture modules written to
``tmp_path`` — each fixture isolates one hazard shape the repo actually
uses (kernel factories, donated buffers, ``enable_x64`` scoping, static
float args) plus the clean twin that must NOT be flagged.  The dogfood
test pins the acceptance criterion directly: ``python -m repro.analysis
src/`` exits 0 against the committed baseline.
"""

import json
import os

import pytest

from repro.analysis.lint import (LintConfig, apply_baseline, load_baseline,
                                 main as lint_main, run_lint,
                                 write_baseline)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_src(tmp_path, source, config=None, name="mod.py"):
    p = tmp_path / name
    p.write_text(source)
    active, suppressed, _ = run_lint([str(p)], config=config)
    return active, suppressed


def _by_rule(findings, rule):
    return [f for f in findings if f.rule == rule]


# --------------------------------------------------------- use-after-donation
class TestUseAfterDonation:
    def test_read_after_donating_call_flagged(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import functools
import jax

@functools.partial(jax.jit, donate_argnums=(0,))
def upd(buf, x):
    return buf + x

def bad(b, x):
    out = upd(b, x)
    return b + out

def good(b, x):
    b = upd(b, x)
    return b + 1
""")
        found = _by_rule(active, "use-after-donation")
        assert len(found) == 1
        assert "`b`" in found[0].message and "upd" in found[0].message

    def test_factory_kernel_and_same_statement_rebind(self, tmp_path):
        """The repo's `_KERNEL_CACHE` idiom: a factory returns an inner
        jitted def with donations; call sites bind it to a local name.
        Rebinding in the donating statement itself is the safe pattern."""
        active, _ = _lint_src(tmp_path, """
import functools
import jax

def _scatter_fn():
    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(buf, rows, vals):
        return buf.at[rows].set(vals)
    return scatter

class State:
    def safe(self, rows, vals):
        scatter = _scatter_fn()
        self._dbuf = scatter(self._dbuf, rows, vals)
        return self._dbuf

    def leak(self, rows, vals):
        scatter = _scatter_fn()
        out = scatter(self._dbuf, rows, vals)
        return self._dbuf.sum() + out.sum()
""")
        found = _by_rule(active, "use-after-donation")
        assert len(found) == 1
        assert "self._dbuf" in found[0].message

    def test_rebind_on_next_line_is_safe(self, tmp_path):
        """The drain idiom: donate, unpack fresh buffers, rebind before
        any read."""
        active, _ = _lint_src(tmp_path, """
import functools
import jax

@functools.partial(jax.jit, donate_argnums=(2,))
def kernel(a, b, admit):
    return a, admit * 2

class S:
    def drain(self):
        out, admit_new = kernel(self._a, self._b, self._dadmit)
        self._dadmit = admit_new
        return out
""")
        assert _by_rule(active, "use-after-donation") == []


# ----------------------------------------------------- host-sync-in-hot-path
_SYNC_CFG = LintConfig(entry_points=((None, "loop"),), allow_paths=(),
                       allow_funcs=("bench_",))


class TestHostSyncInHotPath:
    SRC = """
import jax
import numpy as np

@jax.jit
def step(x):
    return x * 2

def helper(x):
    y = step(x)
    return np.asarray(y)

def loop(x):
    for _ in range(3):
        x = helper(x)
    v = step(x)
    return v.item()

def bench_probe(x):
    return np.asarray(step(x))

def unreachable(x):
    y = step(x)
    return np.asarray(y)
"""

    def test_reachable_syncs_flagged_allowlist_respected(self, tmp_path):
        active, _ = _lint_src(tmp_path, self.SRC, config=_SYNC_CFG)
        found = _by_rule(active, "host-sync-in-hot-path")
        msgs = sorted(f.message for f in found)
        assert len(found) == 2, msgs
        assert any("np.asarray" in m for m in msgs)  # helper (reachable)
        assert any(".item()" in m for m in msgs)     # loop (entry itself)
        # bench_ prefix and the unreachable function stay silent

    def test_bound_method_dispatch_counts_as_reachable(self, tmp_path):
        """``engine = self._run; engine(x)`` must not hide the callee."""
        active, _ = _lint_src(tmp_path, """
import jax
import numpy as np

@jax.jit
def step(x):
    return x + 1

class Sim:
    def loop(self, x):
        engine = self._run
        return engine(x)

    def _run(self, x):
        v = step(x)
        return float(v)
""", config=LintConfig(entry_points=(("Sim", "loop"),), allow_paths=(),
                       allow_funcs=()))
        found = _by_rule(active, "host-sync-in-hot-path")
        assert len(found) == 1 and "float" in found[0].message

    def test_device_get_is_a_declared_sync(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import jax

@jax.jit
def step(x):
    return x

def loop(x):
    return jax.device_get(step(x))
""", config=_SYNC_CFG)
        assert len(_by_rule(active, "host-sync-in-hot-path")) == 1


# ------------------------------------------------------------------ x64-scope
class TestX64Scope:
    def test_outside_scope_flagged_inside_clean(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import jax
import jax.numpy as jnp


def good():
    with jax.enable_x64(True):
        return jnp.zeros(4, jnp.float64)

def bad():
    a = jnp.asarray([1.0], dtype="float64")
    return a + jnp.float64(2.0)
""")
        found = _by_rule(active, "x64-scope")
        assert len(found) == 2
        assert all(f.line >= 10 for f in found)  # both in bad()

    def test_runtime_guard_suppresses(self, tmp_path):
        """predictor.py idiom: dtype picked off jax.config at runtime."""
        active, _ = _lint_src(tmp_path, """
import jax
import jax.numpy as jnp

def pick():
    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    return dtype
""")
        assert _by_rule(active, "x64-scope") == []

    def test_pure_numpy_module_ignored(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import numpy as np

PAD = np.float64(1e30)

def host_math(x):
    return np.asarray(x, np.float64)
""")
        assert _by_rule(active, "x64-scope") == []


# ----------------------------------------------- tracer-unsafe control flow
class TestTracerUnsafeControlFlow:
    def test_branch_on_jit_result_flagged(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import jax

@jax.jit
def pred(x):
    return x > 0

def bad(x):
    flag = pred(x)
    if flag:
        return 1
    return 0

def converted(x):
    flag = pred(x)
    if bool(flag):
        return 1
    return 0

def host_only(x):
    n = len(x)
    while n > 0:
        n -= 1
    return n
""")
        found = _by_rule(active, "tracer-unsafe-control-flow")
        assert len(found) == 1
        assert "`flag`" in found[0].message and "`if`" in found[0].message

    def test_while_on_jit_result_flagged(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import jax

@jax.jit
def step(x):
    return x - 1

def bad(x):
    x = step(x)
    while x:
        x = step(x)
    return x
""")
        found = _by_rule(active, "tracer-unsafe-control-flow")
        assert found and "`while`" in found[0].message


# ----------------------------------------------------------- recompile-hazard
class TestRecompileHazard:
    def test_float_static_arg_flagged(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("dt", "mode"))
def f(x, *, dt: float = 1.0, mode: str = "a"):
    return x * dt
""")
        found = _by_rule(active, "recompile-hazard")
        assert len(found) == 1
        assert "`dt: float`" in found[0].message  # mode: str is fine

    def test_unhashable_static_arg_flagged(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import functools
import jax

@functools.partial(jax.jit, static_argnums=(1,))
def g(x, opts: list):
    return x
""")
        found = _by_rule(active, "recompile-hazard")
        assert found and "unhashable" in found[0].message

    def test_raw_len_shape_feeding_jit_flagged(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def run(buf):
    return buf.sum()

def _bucket(n, lo=8):
    return max(lo, 1 << (n - 1).bit_length())

def bad(xs):
    buf = np.zeros((len(xs), 4))
    return run(jnp.asarray(buf))

def good(xs):
    buf = np.zeros((_bucket(len(xs)), 4))
    return run(jnp.asarray(buf))
""")
        found = _by_rule(active, "recompile-hazard")
        assert len(found) == 1
        assert "`buf`" in found[0].message and "len()" in found[0].message


# ---------------------------------------------------- suppressions + baseline
class TestSuppressionsAndBaseline:
    SRC = """
import jax

@jax.jit
def step(x):
    return x

def loop(x):
    y = step(x)
    a = float(y)  # lint: allow[host-sync-in-hot-path] readback is the API
    # lint: allow[host-sync-in-hot-path] standalone comment form
    b = float(y)
    c = float(y)
    return a + b + c
"""

    def test_inline_allow_suppresses_with_reason(self, tmp_path):
        active, suppressed = _lint_src(tmp_path, self.SRC, config=_SYNC_CFG)
        assert len(suppressed) == 2  # same-line and next-line forms
        remaining = _by_rule(active, "host-sync-in-hot-path")
        assert len(remaining) == 1  # the un-suppressed float(y)

    def test_bare_allow_is_itself_a_finding(self, tmp_path):
        active, _ = _lint_src(tmp_path, """
def f():
    return 1  # lint: allow[x64-scope]
""")
        found = _by_rule(active, "bare-suppression")
        assert found and "justification" in found[0].message

    def test_wrong_rule_allow_does_not_suppress(self, tmp_path):
        active, suppressed = _lint_src(tmp_path, """
import jax

@jax.jit
def step(x):
    return x

def loop(x):
    y = step(x)
    return float(y)  # lint: allow[x64-scope] wrong rule named
""", config=_SYNC_CFG)
        assert suppressed == []
        assert len(_by_rule(active, "host-sync-in-hot-path")) == 1

    def test_baseline_ratchet(self, tmp_path):
        active, _ = _lint_src(tmp_path, self.SRC, config=_SYNC_CFG)
        findings = _by_rule(active, "host-sync-in-hot-path")
        assert len(findings) == 1
        key = findings[0].key

        # equal count -> clean; over -> new; under -> stale
        new, baselined, stale = apply_baseline(
            findings, {key: {"count": 1, "why": "pinned"}})
        assert new == [] and baselined == [key] and stale == []
        new, _, _ = apply_baseline(findings, {})
        assert new == findings
        new, _, stale = apply_baseline(
            findings, {key: {"count": 3, "why": "was worse"}})
        assert new == [] and len(stale) == 1 and "shrink" in stale[0]

    def test_write_and_load_roundtrip(self, tmp_path):
        active, _ = _lint_src(tmp_path, self.SRC, config=_SYNC_CFG)
        findings = _by_rule(active, "host-sync-in-hot-path")
        bpath = tmp_path / "baseline.json"
        write_baseline(str(bpath), findings,
                       {findings[0].key: {"count": 9, "why": "kept"}})
        data = load_baseline(str(bpath))
        assert data[findings[0].key] == {"count": 1, "why": "kept"}
        raw = json.loads(bpath.read_text())
        assert raw["_comment"]  # self-describing file


# ----------------------------------------------------------------- dogfooding
class TestDogfood:
    def test_repo_src_exits_zero(self, monkeypatch):
        """Acceptance criterion: `python -m repro.analysis src/` is clean
        against the committed baseline — and strictly so (no stale
        entries; the ratchet is tight)."""
        monkeypatch.chdir(REPO_ROOT)
        assert lint_main(["src", "--strict"]) == 0

    def test_repo_findings_all_have_reasons(self, monkeypatch):
        monkeypatch.chdir(REPO_ROOT)
        baseline = load_baseline("analysis_baseline.json")
        assert baseline  # the intentional findings are recorded
        for key, entry in baseline.items():
            assert entry["why"] and not entry["why"].startswith("TODO"), key

    def test_list_rules_runs(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule_name in ("use-after-donation", "host-sync-in-hot-path",
                          "x64-scope", "tracer-unsafe-control-flow",
                          "recompile-hazard", "unguarded-obs-in-hot-path"):
            assert rule_name in out

    def test_new_finding_fails_the_gate(self, tmp_path, monkeypatch):
        p = tmp_path / "regression.py"
        # `simulate_fleet_many` is one of the default entry roots, so
        # the sync is in the hot path under the shipped config.
        p.write_text("""
import jax

@jax.jit
def step(x):
    return x

def simulate_fleet_many(x):
    return step(x).item()
""")
        monkeypatch.chdir(tmp_path)
        rc = lint_main([str(p), "--baseline", str(tmp_path / "none.json")])
        assert rc == 1


# ---------------------------------------------------- unguarded-obs-in-hot-path
_OBS_CFG = LintConfig(entry_points=((None, "loop"),), allow_paths=(),
                      allow_funcs=("bench_",))


class TestUnguardedObsInHotPath:
    SRC = """
from repro.obs import metrics as _met
from repro.obs import trace as _obs

def helper():
    _obs.instant("tick")          # reachable via loop -> flagged

def loop(x):
    helper()
    with _obs.span("work"):       # unguarded -> flagged
        x = x + 1
    if _obs.enabled:
        _met.counter("c").inc()   # guarded -> clean
        with _obs.span("ok") as sp:
            sp.add(n=1)
    return x

def unreachable(x):
    _met.gauge("g").set(x)        # not in the hot path -> silent

def bench_loop(x):
    _obs.instant("bench")         # allow_funcs prefix -> silent
"""

    def test_unguarded_calls_flagged_guarded_clean(self, tmp_path):
        active, _ = _lint_src(tmp_path, self.SRC, config=_OBS_CFG)
        found = _by_rule(active, "unguarded-obs-in-hot-path")
        msgs = sorted(f.message for f in found)
        assert len(found) == 2, msgs
        assert any("_obs.instant" in m and "helper" in m for m in msgs)
        assert any("_obs.span" in m and "loop" in m for m in msgs)

    def test_obs_subsystem_itself_exempt(self, tmp_path):
        sub = tmp_path / "repro" / "obs"
        sub.mkdir(parents=True)
        p = sub / "trace.py"
        p.write_text("""
def span(name):
    import trace
    trace.instant("self")
""")
        active, _, _ = run_lint([str(p)], config=_OBS_CFG)
        assert _by_rule(active, "unguarded-obs-in-hot-path") == []

    def test_dogfooded_instrumentation_is_guarded(self):
        """The repo's own hot-path instrumentation must satisfy the rule
        it ships — the shipped entry points cover cluster/admission/
        fleet/serve."""
        paths = [os.path.join(REPO_ROOT, "src", "repro", p) for p in
                 ("sched/cluster.py", "sched/admission.py",
                  "core/fleet.py", "serve/batcher.py", "serve/server.py")]
        active, _, _ = run_lint(paths)
        assert _by_rule(active, "unguarded-obs-in-hot-path") == []
