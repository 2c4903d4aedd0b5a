"""jax-lint rule family: one positive + one negative fixture per rule,
the two resurrected PR 6 bug fixtures (closure constant-fold,
donation-then-read), and the per-family baseline mechanics.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from ray_tpu.devtools import invariants as inv
from ray_tpu.devtools import lint
from ray_tpu.devtools.jaxlint import lint_source

CORE = "ray_tpu.serve.engine.core"   # declared hot-path module
GRAFT = "__graft_entry__"            # declared rng-single-init module


def rules(findings):
    return sorted({f.rule for f in findings})


# ----------------------------------------- closure-captured-array-into-jit


def test_pr6_constant_fold_regression_caught():
    """The EXACT PR 6 bug shape: the int8 decode-matmul bench closed
    over the quantized weight, jit constant-folded it to full width and
    the 'int8' timing silently streamed full-precision bytes."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def bench(x):\n"
        "    wq = jnp.clip(jnp.round(x * 127), -127, 127)"
        ".astype(jnp.int8)\n"
        "    f = jax.jit(lambda s: s @ wq.astype(s.dtype))\n"
        "    return f(x)\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["closure-captured-array-into-jit"]
    assert "'wq'" in fs[0].message and "constant" in fs[0].message


def test_array_as_jit_argument_clean():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def bench(x):\n"
        "    wq = jnp.zeros((4, 4), jnp.int8)\n"
        "    f = jax.jit(lambda s, w: s @ w.astype(s.dtype))\n"
        "    return f(x, wq)\n")
    assert lint_source(src, "m", "m.py") == []


def test_module_level_array_into_decorated_jit_flagged():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "TABLE = np.arange(100)\n"
        "@jax.jit\n"
        "def lookup(x):\n"
        "    return TABLE[x]\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["closure-captured-array-into-jit"]
    assert "'TABLE'" in fs[0].message


def test_self_attribute_capture_flagged():
    src = (
        "import jax\n"
        "class M:\n"
        "    def go(self, x):\n"
        "        f = jax.jit(lambda y: y + self.weights)\n"
        "        return f(x)\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["closure-captured-array-into-jit"]
    assert "self.weights" in fs[0].message


def test_scalar_and_config_captures_clean():
    src = (
        "import jax\n"
        "def go(x):\n"
        "    n = 4\n"
        "    cfg = make_config()\n"
        "    f = jax.jit(lambda y: y * n + cfg.eps)\n"
        "    return f(x)\n")
    assert lint_source(src, "m", "m.py") == []


def test_named_local_function_target_resolved():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def build():\n"
        "    w = jnp.ones((2, 2))\n"
        "    def fwd(x):\n"
        "        return x @ w\n"
        "    return jax.jit(fwd)\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["closure-captured-array-into-jit"]


# --------------------------------------------------- donation-then-read


def test_pr6_donation_then_read_regression_caught():
    """The PR 6 dryrun bug shape: the donating train step consumed the
    state's buffers, then the function read the donated input again."""
    src = (
        "import jax\n"
        "def run(step_fn, state, tokens):\n"
        "    step = jax.jit(step_fn, donate_argnums=(0,))\n"
        "    new_state, metrics = step(state, tokens)\n"
        "    return state.params\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["donation-then-read"]
    assert "'state.params'" in fs[0].message
    assert "donated" in fs[0].message


def test_donation_with_rebind_clean():
    src = (
        "import jax\n"
        "def run(step_fn, state, tokens):\n"
        "    step = jax.jit(step_fn, donate_argnums=(0,))\n"
        "    for _ in range(3):\n"
        "        state, metrics = step(state, tokens)\n"
        "    return state.params\n")
    assert lint_source(src, "m", "m.py") == []


def test_decorated_partial_donation_tracked():
    src = (
        "import functools\n"
        "import jax\n"
        "def run(s, t):\n"
        "    @functools.partial(jax.jit, donate_argnums=(0,))\n"
        "    def step(a, b):\n"
        "        return a\n"
        "    out = step(s, t)\n"
        "    return s\n")
    fs = lint_source(src, "m", "m.py")
    assert rules(fs) == ["donation-then-read"]


def test_non_donated_positions_clean():
    src = (
        "import jax\n"
        "def run(step_fn, state, tokens):\n"
        "    step = jax.jit(step_fn, donate_argnums=(0,))\n"
        "    out = step(state, tokens)\n"
        "    return tokens\n")  # position 1 is not donated
    assert lint_source(src, "m", "m.py") == []


# ------------------------------------------------- host-sync-in-hot-path


def test_hot_path_syncs_flagged():
    src = (
        "import numpy as np\n"
        "class E:\n"
        "    def _decode_tick(self):\n"
        "        toks, self.cache = self.loop.decode_chunk(self.params)\n"
        "        if toks > 0:\n"
        "            x = float(toks)\n"
        "        y = np.asarray(toks)\n"
        "        z = self._jax.device_get(toks)\n"
        "        w = toks.item()\n")
    fs = lint_source(src, CORE, "core.py")
    assert [f.rule for f in fs] == ["host-sync-in-hot-path"] * 5


def test_fetched_values_host_side_clean():
    src = (
        "class E:\n"
        "    def _decode_tick(self):\n"
        "        toks_d, nv_d = self.loop.decode_chunk(self.params)\n"
        "        toks, nv = self._fetch((toks_d, nv_d))\n"
        "        if nv > 0:\n"
        "            n = int(toks[0])\n")
    assert lint_source(src, CORE, "core.py") == []


def test_hot_set_is_reachability_not_module_wide():
    src = (
        "class E:\n"
        "    def _decode_tick(self):\n"
        "        self._helper()\n"
        "    def _helper(self):\n"
        "        x = self.loop.decode_chunk(1)\n"
        "        x.item()\n"
        "    def offline_debug(self):\n"
        "        y = self.loop.decode_chunk(1)\n"
        "        y.item()\n")
    fs = lint_source(src, CORE, "core.py")
    assert len(fs) == 1 and fs[0].scope == "_helper"
    # And the whole rule is scoped to declared hot-path modules.
    assert lint_source(src, "ray_tpu.util.queue", "q.py") == []


def test_intended_sync_allow_comment_honored():
    src = (
        "class E:\n"
        "    def _decode_tick(self):\n"
        "        x = self.loop.decode_chunk(1)\n"
        "        jax.device_get(x)  "
        "# rtpu-lint: disable=host-sync-in-hot-path\n")
    assert lint_source(src, CORE, "core.py") == []


# The engine's four mechanisms live in modules of their own: module ->
# the methods the tick calls them by (engine/README.md's call sites).
MECHANISMS = {
    "ray_tpu.serve.engine.drafter": {"drafts", "tick"},
    "ray_tpu.serve.engine.kv_fleet": {"extend", "spill_evicted",
                                      "note_prefill_cost"},
    "ray_tpu.serve.engine.handoff": {"finish", "tick"},
    "ray_tpu.serve.engine.preempt": {"park", "resume"},
}


@pytest.mark.parametrize("module", MECHANISMS)
def test_a_mechanisms_tick_entries_are_hot_path_roots(module):
    """Code that left `core.py` did not leave the host-sync rule: each
    entry the tick calls is a declared root that the real module
    defines, and a planted ``np.asarray`` on a device value there, or
    in a helper it calls, is a finding."""
    assert inv.JAX_HOT_PATH_ROOTS[module] == MECHANISMS[module]
    assert module in inv.RES_REGISTRY_MODULES
    real = ast.parse(open(importlib.util.find_spec(module).origin).read())
    defined = {n.name for n in ast.walk(real)
               if isinstance(n, ast.FunctionDef)}
    assert MECHANISMS[module] <= defined
    for entry in sorted(MECHANISMS[module]):
        src = (
            "import numpy as np\n"
            "class Mechanism:\n"
            f"    def {entry}(self):\n"
            "        return self._helper()\n"
            "    def _helper(self):\n"
            "        eng = self.engine\n"
            "        toks, eng.cache = eng.loop.decode_chunk(eng.params)\n"
            "        return np.asarray(toks)\n"
            "    def offline(self):\n"
            "        return np.asarray(self.engine.loop.decode_chunk(1))\n")
        fs = lint_source(src, module, "mechanism.py")
        assert [(f.rule, f.scope) for f in fs] == [
            ("host-sync-in-hot-path", "_helper")], (entry, fs)


# ---------------------------------------- unclamped-dynamic-update-slice


def test_unclamped_dus_flagged():
    src = (
        "from jax import lax\n"
        "def write(cache, row, idx):\n"
        "    a = lax.dynamic_update_slice(cache, row, (0, idx))\n"
        "    b = lax.dynamic_update_slice_in_dim(cache, row, idx, "
        "axis=1)\n"
        "    return a, b\n")
    fs = lint_source(src, "m", "m.py")
    assert [f.rule for f in fs] == ["unclamped-dynamic-update-slice"] * 2
    assert "CLAMPS" in fs[0].message


def test_clamped_or_constant_dus_clean():
    src = (
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def write(cache, row, idx, n):\n"
        "    a = lax.dynamic_update_slice(cache, row, (0, 0))\n"
        "    b = lax.dynamic_update_slice_in_dim(\n"
        "        cache, row, jnp.minimum(idx, n - 1), axis=1)\n"
        "    c = lax.dynamic_update_slice(\n"
        "        cache, row, (0, jnp.clip(idx, 0, n)))\n"
        "    return a, b, c\n")
    assert lint_source(src, "m", "m.py") == []


def test_dus_allow_comment_honored():
    src = (
        "from jax import lax\n"
        "def write(cache, row, idx):\n"
        "    return lax.dynamic_update_slice(cache, row, (0, idx))  "
        "# rtpu-lint: disable=unclamped-dynamic-update-slice\n")
    assert lint_source(src, "m", "m.py") == []


# -------------------------------------------------- pallas-shape-rules


def test_pallas_kernel_shape_hazards_flagged():
    src = (
        "import jax.numpy as jnp\n"
        "import jax.experimental.pallas as pl\n"
        "def _kern(x_ref, o_ref):\n"
        "    i = jnp.arange(8)\n"
        "    s = jnp.sum(x_ref[...], axis=-1)\n"
        "    o_ref[...] = x_ref[...].reshape(4, 2)\n"
        "def run(x, shape):\n"
        "    return pl.pallas_call(_kern, out_shape=shape)(x)\n")
    fs = lint_source(src, "m", "m.py")
    assert [f.rule for f in fs] == ["pallas-shape-rules"] * 3
    msgs = " ".join(f.message for f in fs)
    assert "broadcasted_iota" in msgs and "keepdims" in msgs \
        and "reshape" in msgs


def test_pallas_kernel_disciplined_body_clean():
    # The idioms the repo's real kernels use: keepdims reductions,
    # broadcasted_iota, no reshape. Kernel wrapped in functools.partial
    # exactly like ops/fused.py does.
    src = (
        "import functools\n"
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "import jax.experimental.pallas as pl\n"
        "def _kern(x_ref, o_ref, *, eps):\n"
        "    v = jnp.mean(x_ref[...], axis=-1, keepdims=True)\n"
        "    i = lax.broadcasted_iota(jnp.int32, (1, 8), 1)\n"
        "    o_ref[...] = x_ref[...] * lax.rsqrt(v + eps)\n"
        "def run(x, shape):\n"
        "    return pl.pallas_call(functools.partial(_kern, eps=1e-5),"
        " out_shape=shape)(x)\n")
    assert lint_source(src, "m", "m.py") == []


def test_pallas_kernel_float_iota_flagged():
    # What the v5e compiler refused in the old fused_qk_rope: tpu.iota
    # yields integers only (the interpreter accepts a float one).
    src = (
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "import jax.experimental.pallas as pl\n"
        "def _kern(x_ref, o_ref):\n"
        "    e = lax.broadcasted_iota(jnp.float32, (1, 8), 1)\n"
        "    o_ref[...] = x_ref[...] * e\n"
        "def run(x, shape):\n"
        "    return pl.pallas_call(_kern, out_shape=shape)(x)\n")
    fs = lint_source(src, "m", "m.py")
    assert [f.rule for f in fs] == ["pallas-shape-rules"]
    assert "float iota" in fs[0].message


def test_reshape_outside_kernel_clean():
    src = (
        "def host_side(x):\n"
        "    return x.reshape(-1, 4)\n")
    assert lint_source(src, "m", "m.py") == []


# --------------------------------------------------- rng-reinit-per-mesh


def test_prngkey_inside_mesh_context_flagged():
    src = (
        "import jax\n"
        "def dryrun(mesh_context, mesh):\n"
        "    with mesh_context(mesh):\n"
        "        key = jax.random.PRNGKey(0)\n")
    fs = lint_source(src, GRAFT, "g.py")
    assert rules(fs) == ["rng-reinit-per-mesh"]
    assert "device_put ONE host init" in fs[0].message


def test_single_host_init_device_put_clean():
    src = (
        "import jax\n"
        "def dryrun(mesh_context, mesh, shardings):\n"
        "    key0 = jax.random.PRNGKey(0)\n"
        "    with mesh_context(mesh):\n"
        "        params = jax.device_put(init(key0), shardings)\n")
    assert lint_source(src, GRAFT, "g.py") == []


def test_rng_rule_scoped_to_declared_modules():
    src = (
        "import jax\n"
        "def f(mesh_context, mesh):\n"
        "    with mesh_context(mesh):\n"
        "        key = jax.random.PRNGKey(0)\n")
    assert lint_source(src, "ray_tpu.other", "o.py") == []


# -------------------------------------------------- family machinery


def _conc_finding():
    return lint.lint_source(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n", "m", "m.py")


def _jax_finding():
    return lint_source(
        "from jax import lax\n"
        "def w(c, r, i):\n"
        "    return lax.dynamic_update_slice(c, r, (0, i))\n",
        "m", "m.py")


def test_unified_baseline_sections_and_merge(tmp_path):
    bpath = str(tmp_path / "base.json")
    lint.write_baseline(bpath, _conc_finding() + _jax_finding())
    data = json.load(open(bpath))
    assert data["version"] == 2
    assert len(data["families"]["concurrency"]["findings"]) == 1
    assert len(data["families"]["jax"]["findings"]) == 1
    # load_baseline merges the sections for budget checking.
    merged = lint.load_baseline(bpath)
    assert len(merged) == 2
    assert lint.new_findings(_conc_finding() + _jax_finding(),
                             merged) == []


def test_per_family_write_preserves_other_family(tmp_path):
    """The per-family analog of the PR 5 partial-path hazard: a jax-only
    --write-baseline must carry the concurrency section over verbatim."""
    bpath = str(tmp_path / "base.json")
    lint.write_baseline(bpath, _conc_finding() + _jax_finding())
    before = json.load(open(bpath))["families"]["concurrency"]
    # Rewrite ONLY the jax section, from a run with zero jax findings.
    lint.write_baseline(bpath, [], families=("jax",))
    data = json.load(open(bpath))
    assert data["families"]["concurrency"] == before
    assert data["families"]["jax"]["findings"] == {}


def test_v1_flat_baseline_still_loads_and_upgrades(tmp_path):
    bpath = tmp_path / "base.json"
    findings = _conc_finding()
    table = {f.fingerprint(): {"count": 1, "rule": f.rule,
                               "path": f.path, "message": f.message}
             for f in findings}
    bpath.write_text(json.dumps({"version": 1, "findings": table}))
    assert lint.new_findings(findings, lint.load_baseline(
        str(bpath))) == []
    # A jax-only partial write of a v1 file keeps the flat findings as
    # the concurrency section.
    lint.write_baseline(str(bpath), _jax_finding(), families=("jax",))
    data = json.loads(bpath.read_text())
    assert data["families"]["concurrency"]["findings"] == table
    assert len(data["families"]["jax"]["findings"]) == 1


def test_partial_family_write_refuses_corrupt_existing(tmp_path):
    """A corrupt existing baseline must REFUSE a partial-family
    rewrite (treating it as empty would silently drop the other
    family's entire debt — the truncation hazard class again)."""
    import pytest

    bpath = tmp_path / "base.json"
    bpath.write_text("{ corrupt json <<<<")
    with pytest.raises(ValueError, match="unreadable"):
        lint.write_baseline(str(bpath), _jax_finding(),
                            families=("jax",))
    assert bpath.read_text() == "{ corrupt json <<<<"  # untouched
    # Non-dict JSON counts as corrupt for a partial write too, and a
    # FULL rewrite of either recovers gracefully (nothing carried).
    bpath.write_text("null")
    with pytest.raises(ValueError, match="unreadable"):
        lint.write_baseline(str(bpath), _jax_finding(),
                            families=("jax",))
    lint.write_baseline(str(bpath), _jax_finding())
    # A valid-but-EMPTY '{}' baseline is not corrupt: partial writes
    # proceed, as do partial writes of a missing file.
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    lint.write_baseline(str(empty), _jax_finding(), families=("jax",))
    assert len(json.loads(empty.read_text())
               ["families"]["jax"]["findings"]) == 1
    lint.write_baseline(str(tmp_path / "fresh.json"), _jax_finding(),
                        families=("jax",))


def test_syntax_error_reported_by_every_family(tmp_path):
    """A jax-only run must not exit 0 on a file it could not parse."""
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    root, _ = lint.default_roots()
    for fams in (("jax",), ("concurrency",)):
        findings = lint.lint_paths([str(bad)], root, families=fams)
        assert len(findings) == 1 and \
            "syntax error" in findings[0].message, fams


def test_schema_mismatch_isolates_families(tmp_path, capsys):
    """A stale fingerprint-scheme in ONE family's section is ignored on
    load (its debt reports as new -> regenerate that family) while the
    other family's section keeps matching — the isolation the
    per-family schema version exists to provide."""
    bpath = str(tmp_path / "base.json")
    lint.write_baseline(bpath, _conc_finding() + _jax_finding())
    data = json.load(open(bpath))
    data["families"]["jax"]["schema"] = 999  # stale scheme
    open(bpath, "w").write(json.dumps(data))
    merged = lint.load_baseline(bpath)
    assert lint.new_findings(_conc_finding(), merged) == []
    assert len(lint.new_findings(_jax_finding(), merged)) == 1
    assert "regenerate with --family jax" in capsys.readouterr().err


def test_cli_family_selection(tmp_path):
    """--family jax must not see (or fail on) a concurrency violation,
    and vice versa."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n")
    bpath = tmp_path / "base.json"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    base = [sys.executable, "-m", "ray_tpu.devtools.lint", str(bad),
            "--baseline", str(bpath)]
    r = subprocess.run(base + ["--family", "jax"], env=env, cwd=repo,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(base + ["--family", "concurrency"], env=env,
                       cwd=repo, capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr


def test_rule_family_map_is_total():
    assert set(lint.RULE_FAMILY) == (set(lint.RULES) | set(lint.JAX_RULES)
                                     | set(lint.DIST_RULES)
                                     | set(lint.RES_RULES)
                                     | set(lint.CHAN_RULES))
    for rule in lint.RULES:
        assert lint.RULE_FAMILY[rule] == "concurrency"
    for rule in lint.JAX_RULES:
        assert lint.RULE_FAMILY[rule] == "jax"
    for rule in lint.DIST_RULES:
        assert lint.RULE_FAMILY[rule] == "dist"
    for rule in lint.RES_RULES:
        assert lint.RULE_FAMILY[rule] == "res"
