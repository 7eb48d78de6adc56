"""Static-analysis pass: per-rule must-flag/must-pass fixtures, the waiver
grammar, exclusion-list sync with pyproject, and the jaxpr contract checker
(clean on the real engine, failing on injected corruptions)."""
import dataclasses
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import lint_paths
from repro.analysis.config import EXCLUDED_DIRS
from repro.analysis.lint import lint_file

_REPO = Path(__file__).resolve().parent.parent


def run_rules(tmp_path, rel, source):
    """Lint a fixture as if it lived at ``src/repro/<rel>``."""
    path = tmp_path / Path(rel).name
    path.write_text(textwrap.dedent(source))
    return lint_file(path, rel, rel)


def rule_ids(violations):
    return [v.rule for v in violations]


# -- R1: trace containment ----------------------------------------------------

R1_SOURCE = """\
    import jax

    def build(fn):
        return jax.jit(fn)
    """


def test_r1_flags_jit_outside_runtime(tmp_path):
    violations, _ = run_rules(tmp_path, "core/foo.py", R1_SOURCE)
    assert rule_ids(violations) == ["R1"]
    assert "executable cache" in violations[0].message
    assert violations[0].render().startswith("core/foo.py:4 R1 ")


def test_r1_allows_jit_in_runtime_and_kernels(tmp_path):
    for rel in ("runtime/foo.py", "kernels/foo.py"):
        violations, _ = run_rules(tmp_path, rel, R1_SOURCE)
        assert violations == []


def test_r1_flags_decorator_and_shard_map(tmp_path):
    violations, _ = run_rules(tmp_path, "api/foo.py", """\
        import jax
        from jax import shard_map

        @jax.jit
        def f(x):
            return x

        def g(fn, mesh, spec):
            return shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec)
        """)
    assert rule_ids(violations) == ["R1", "R1"]


# -- R2: accumulation discipline ----------------------------------------------

def test_r2_flags_dtype_free_sum_and_uncast_psum(tmp_path):
    violations, _ = run_rules(tmp_path, "core/fct.py", """\
        import jax.numpy as jnp
        from jax import lax

        def histogram(w, hist):
            total = jnp.sum(w)
            return total + lax.psum(hist, "w")
        """)
    assert rule_ids(violations) == ["R2", "R2"]
    assert "dtype" in violations[0].message


def test_r2_passes_explicit_policy_dtype(tmp_path):
    violations, _ = run_rules(tmp_path, "core/fct.py", """\
        import jax.numpy as jnp
        from jax import lax

        def histogram(w, hist, dt):
            total = jnp.sum(w, dtype=dt)
            return total + lax.psum(hist.astype(dt), "w")

        def padded(hist, dt, pad):
            h = hist.astype(dt)
            h = jnp.pad(h, pad)
            return lax.psum_scatter(h, "w", tiled=True)
        """)
    assert violations == []


def test_r2_unblesses_reassigned_operand(tmp_path):
    # the cast is overwritten before the collective -> flagged again
    violations, _ = run_rules(tmp_path, "core/fct.py", """\
        from jax import lax

        def histogram(w, hist, dt):
            h = hist.astype(dt)
            h = hist * 2
            return lax.psum(h, "w")
        """)
    assert rule_ids(violations) == ["R2"]


def test_r2_scoped_to_accum_modules(tmp_path):
    violations, _ = run_rules(tmp_path, "core/star.py", """\
        import jax.numpy as jnp

        def f(w):
            return jnp.sum(w)
        """)
    assert violations == []


# -- R3: lock discipline ------------------------------------------------------

def test_r3_flags_unlocked_counter_and_field(tmp_path):
    violations, _ = run_rules(tmp_path, "serve/gateway.py", """\
        class Gateway:
            def submit(self, key, fut):
                self.submitted += 1
                self._pending[key] = fut
        """)
    assert rule_ids(violations) == ["R3", "R3"]
    assert "self._lock" in violations[0].message


def test_r3_passes_locked_and_constructor_writes(tmp_path):
    violations, _ = run_rules(tmp_path, "serve/gateway.py", """\
        import threading

        class Gateway:
            def __init__(self):
                self._lock = threading.Lock()
                self.submitted = 0
                self._pending = {}

            def submit(self, key, fut):
                with self._lock:
                    self.submitted += 1
                    self._pending[key] = fut
        """)
    assert violations == []


def test_r3_requires_the_configured_lock(tmp_path):
    # a with-block on some other attribute does not count
    violations, _ = run_rules(tmp_path, "serve/gateway.py", """\
        class Gateway:
            def submit(self):
                with self._other:
                    self.submitted += 1
        """)
    assert rule_ids(violations) == ["R3"]


def test_r3_flags_unguarded_metric_bump_in_serve(tmp_path):
    # growing a raw counter on a serve component instead of routing it
    # through the metrics registry (the blessed lock owner) is flagged
    violations, _ = run_rules(tmp_path, "serve/batcher.py", """\
        class DynamicBatcher:
            def _flush(self, batch):
                self.windows_flushed += 1
        """)
    assert rule_ids(violations) == ["R3"]


def test_r3_covers_obs_metrics_instruments(tmp_path):
    # obs/metrics.py is a THREADED_MODULE: instrument bumps are clean only
    # under the registry's shared ``_lock`` — an unlocked fast path on the
    # same instrument is flagged
    violations, _ = run_rules(tmp_path, "obs/metrics.py", """\
        class Counter:
            def inc(self, n=1):
                with self._lock:
                    self._value += n

            def inc_unlocked(self, n=1):
                self._value += n
        """)
    assert rule_ids(violations) == ["R3"]


# -- R4: no host sync in dispatch paths ---------------------------------------

def test_r4_flags_host_sync_in_dispatch(tmp_path):
    violations, _ = run_rules(tmp_path, "runtime/engine.py", """\
        import numpy as np

        def run_batch(self, out):
            np.asarray(out)
            out.block_until_ready()
            return out
        """)
    assert rule_ids(violations) == ["R4", "R4"]


def test_r4_allows_sync_in_collect_functions(tmp_path):
    violations, _ = run_rules(tmp_path, "runtime/engine.py", """\
        import numpy as np

        def _collect(self, out):
            return np.asarray(out)
        """)
    assert violations == []


# -- R5: epoch fencing --------------------------------------------------------

def test_r5_flags_unfenced_cache_put(tmp_path):
    violations, _ = run_rules(tmp_path, "serve/result_cache.py", """\
        class ResultCache:
            def store(self, key, value):
                self._entries.put(key, value)
        """)
    assert rule_ids(violations) == ["R5"]
    assert "generation" in violations[0].message


def test_r5_passes_fenced_puts(tmp_path):
    violations, _ = run_rules(tmp_path, "serve/result_cache.py", """\
        class ResultCache:
            def store_kw(self, key, value, gen):
                self._entries.put(key, value, generation=gen)

            def store_checked(self, key, value, gen):
                if gen != self.generation:
                    return
                self._entries.put(key, value)
        """)
    assert violations == []


def test_r5_flags_unfenced_subscript_assign(tmp_path):
    # the incremental-ingest append path patches cached tuple sets in place
    # via subscript assignment — same insert, different spelling, same rule
    violations, _ = run_rules(tmp_path, "api/session.py", """\
        class FCTSession:
            def patch(self, kws, ts):
                with self._plan_lock:
                    self._tuple_sets[kws] = ts
        """)
    assert rule_ids(violations) == ["R5"]
    assert "_tuple_sets" in violations[0].message


def test_r5_passes_fenced_subscript_assign(tmp_path):
    violations, _ = run_rules(tmp_path, "api/session.py", """\
        class FCTSession:
            def patch(self, kws, ts, epoch):
                with self._plan_lock:
                    assert self._data_epoch == epoch
                    self._tuple_sets[kws] = ts

            def untracked(self, kws):
                with self._plan_lock:
                    self._scratch[kws] = 1   # not a configured cache
        """)
    assert violations == []


# -- waivers ------------------------------------------------------------------

def test_waiver_on_line_or_line_above(tmp_path):
    violations, waived = run_rules(tmp_path, "core/foo.py", """\
        import jax

        f = jax.jit(abs)  # fct-lint: waive[R1] -- fixture same-line reason
        # fct-lint: waive[R1] -- fixture line-above reason
        g = jax.jit(abs)
        """)
    assert violations == []
    assert sorted(w.justification for w in waived) == [
        "fixture line-above reason", "fixture same-line reason"]


def test_waiver_without_justification_is_a_violation(tmp_path):
    violations, waived = run_rules(tmp_path, "core/foo.py", """\
        import jax

        f = jax.jit(abs)  # fct-lint: waive[R1]
        """)
    # the malformed waiver does NOT suppress, and is itself reported
    assert sorted(rule_ids(violations)) == ["R1", "WAIVER"]
    assert waived == []


def test_waiver_must_name_the_right_rule(tmp_path):
    violations, waived = run_rules(tmp_path, "core/foo.py", """\
        import jax

        f = jax.jit(abs)  # fct-lint: waive[R4] -- wrong rule id
        """)
    assert rule_ids(violations) == ["R1"]
    assert waived == []


# -- the repo itself ----------------------------------------------------------

def test_repo_is_lint_clean():
    report = lint_paths(_REPO / "src" / "repro")
    assert report.files_checked > 40
    assert report.ok, "\n".join(v.render() for v in report.violations)
    # every surviving waiver carries a justification by construction
    assert all(w.justification for w in report.waived)


def test_excluded_dirs_match_pyproject():
    """EXCLUDED_DIRS and [tool.ruff] extend-exclude are one policy."""
    text = (_REPO / "pyproject.toml").read_text()
    block = re.search(r"extend-exclude\s*=\s*\[(.*?)\]", text, re.S)
    assert block is not None
    entries = re.findall(r'"([^"]+)"', block.group(1))
    assert sorted(entries) == sorted(
        f"src/repro/{d}" for d in EXCLUDED_DIRS)


# -- layer 2: jaxpr contracts -------------------------------------------------

def _mesh():
    from repro.launch.mesh import make_worker_mesh
    return make_worker_mesh()


def _one_sig():
    from repro.analysis.contracts import representative_signatures
    from repro.core.accum import INT32_CHECKED
    return representative_signatures(1, [INT32_CHECKED])[0]


def test_contracts_clean_on_real_engine():
    from repro.analysis.contracts import check_all_contracts
    failures, checked = check_all_contracts(mesh=_mesh())
    assert checked >= 8  # 4 families x 2 signature buckets per policy
    assert failures == []


def test_contract_c4_rejects_unbucketed_signature():
    from repro.analysis.contracts import check_contract
    sig = _one_sig()
    bad = dataclasses.replace(
        sig, fact=dataclasses.replace(sig.fact, rows=12))
    failures = check_contract("fct_batched", bad, 2, _mesh())
    assert failures and "C4" in failures[0] and "rows=12" in failures[0]


def test_contract_c4_rejects_unbucketed_cn_stack():
    from repro.analysis.contracts import check_contract
    failures = check_contract("fct_batched_percn", _one_sig(), 3, _mesh())
    assert failures and "C4" in failures[0] and "n_stack=3" in failures[0]


def test_contract_c2_catches_float_accumulator(monkeypatch):
    import jax.numpy as jnp

    from repro.analysis.contracts import check_contract
    from repro.core import accum
    monkeypatch.setattr(accum.AccumPolicy, "dtype",
                        property(lambda self: jnp.float32))
    failures = check_contract("fct_batched", _one_sig(), 2, _mesh())
    assert failures and any("C2" in f and "floating-point" in f
                            for f in failures)


def test_contract_c1_catches_double_reduction(monkeypatch):
    from jax import lax

    import repro.runtime.engine as engine_mod
    from repro.analysis.contracts import check_contract
    orig = engine_mod._vmapped_cns

    def doubled(*args, **kwargs):
        return lax.psum(orig(*args, **kwargs), "w")

    monkeypatch.setattr(engine_mod, "_vmapped_cns", doubled)
    failures = check_contract("fct_batched", _one_sig(), 2, _mesh())
    assert failures and any("C1" in f and "reduction" in f for f in failures)


def test_contracts_p8_subprocess():
    """The multidevice CI configuration: all families trace with exactly one
    reduce_scatter and an integer closure at P=8."""
    script = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        from repro.analysis.contracts import check_all_contracts
        failures, checked = check_all_contracts()
        print("RESULT" + json.dumps(
            {"failures": failures, "checked": checked}))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_ENABLE_X64", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT"):])
    assert res["checked"] >= 8
    assert res["failures"] == []


# -- CLI ----------------------------------------------------------------------

def test_cli_exits_zero_and_emits_json():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--json"], env=env,
        capture_output=True, text=True, timeout=120, cwd=_REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] and payload["lint"]["violations"] == []
    assert payload["lint"]["files_checked"] > 40


def test_cli_exits_nonzero_on_violation(tmp_path):
    pkg = tmp_path / "repro"
    (pkg / "core").mkdir(parents=True)
    (pkg / "core" / "bad.py").write_text(
        "import jax\nf = jax.jit(abs)\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(pkg)], env=env,
        capture_output=True, text=True, timeout=120, cwd=_REPO)
    assert proc.returncode == 1
    assert re.search(r"bad\.py:2 R1 ", proc.stdout)
