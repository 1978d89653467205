"""edgelint fixture suite: every rule fires on a seeded violation, stays
quiet on a clean twin, and the real tree is clean (suppressions bounded).

Each EDG rule gets one known-bad and one known-clean snippet laid out in a
tmp mini-tree mirroring the repo layout (``src/repro/core``, ``kernels/``,
``sharding/``) so the scope-sensitive rules see realistic paths.  The
final tests pin the production contract: ``lint_paths`` over the actual
``src/ tests/ benchmarks/`` tree reports zero active findings, and every
suppression carries a written reason.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.edgelint import lint_paths  # noqa: E402


def lint_tree(tmp_path, files: dict[str, str]):
    for rel, source in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(source)
    return lint_paths(["."], root=tmp_path)


def codes(result) -> set[str]:
    return {f.code for f in result.findings}


# ---------------------------------------------------------------------------
# EDG001 — determinism
# ---------------------------------------------------------------------------


def test_edg001_fires_on_host_randomness_in_core(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/core/bad.py": (
                "import numpy as np\n"
                "import time\n"
                "def sample(n):\n"
                "    t = time.time()\n"
                "    return np.random.rand(n) + t\n"
            )
        },
    )
    assert "EDG001" in codes(res)
    assert len([f for f in res.findings if f.code == "EDG001"]) == 2  # clock + rng


def test_edg001_fires_transitively_through_core_imports(tmp_path):
    """A helper module imported by core is inside the deterministic closure."""
    res = lint_tree(
        tmp_path,
        {
            "src/repro/core/engine.py": "from ..util import helper\n",
            "src/repro/util.py": (
                "import time\n\ndef helper():\n    return time.time()\n"
            ),
        },
    )
    assert any(
        f.code == "EDG001" and f.path == "src/repro/util.py" for f in res.findings
    )


def test_edg001_clean_on_threaded_jax_keys_and_seeded_rng(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/core/good.py": (
                "import jax\n"
                "def sample(key, n):\n"
                "    k1, k2 = jax.random.split(key)\n"
                "    return jax.random.uniform(k1, (n,))\n"
            ),
            # outside the core closure, *seeded* host RNG is fine...
            "benchmarks/good_bench.py": (
                "import numpy as np\nrng = np.random.default_rng(0)\n"
            ),
        },
    )
    assert "EDG001" not in codes(res)


def test_edg001_fires_on_unseeded_rng_outside_core(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "benchmarks/bad_bench.py": (
                "import numpy as np\nrng = np.random.default_rng()\n"
            )
        },
    )
    assert "EDG001" in codes(res)


# ---------------------------------------------------------------------------
# EDG002 — host-sync hygiene
# ---------------------------------------------------------------------------

EDG002_BAD = """
import jax
import numpy as np

@jax.jit
def edge_pass(x):
    scale = float(x.sum())
    return np.asarray(x) * scale

def pane_loop(panes):  # edgelint: pane-loop
    return [p.item() for p in panes]
"""

EDG002_CLEAN = """
import jax
import jax.numpy as jnp

@jax.jit
def edge_pass(x, n_dropped):
    host = int(getattr(x, "n_dropped", 0))  # host attribute, not a sync
    return jnp.sum(x) * jnp.float32(host)
"""


def test_edg002_fires_in_jitted_and_pane_loop_functions(tmp_path):
    res = lint_tree(tmp_path, {"src/repro/core/bad_sync.py": EDG002_BAD})
    found = [f for f in res.findings if f.code == "EDG002"]
    assert len(found) >= 3  # float(), np.asarray, .item()
    assert any(".item()" in f.message for f in found)


def test_edg002_clean_on_host_side_casts(tmp_path):
    res = lint_tree(tmp_path, {"src/repro/core/good_sync.py": EDG002_CLEAN})
    assert "EDG002" not in codes(res)


def test_edg002_suppression_requires_the_code(tmp_path):
    sup = EDG002_BAD.replace(
        "scale = float(x.sum())",
        "scale = float(x.sum())  # edgelint: ignore[EDG002] trace boundary",
    )
    res = lint_tree(tmp_path, {"src/repro/core/bad_sync.py": sup})
    assert all("float" not in f.message for f in res.findings if f.code == "EDG002")
    assert any("float" in f.message for f in res.suppressed)
    assert all(s.suppress_reason for s in res.suppressed)


# ---------------------------------------------------------------------------
# EDG003 — accumulator-protocol completeness
# ---------------------------------------------------------------------------

EDG003_BAD = """
from repro.core.estimators import Accumulator, register_accumulator

class HalfKind(Accumulator):
    kind = "half"
    def accumulate(self, values, stratum_idx, mask, num_slots, counts=None):
        return values
    def merge(self, a, b):
        return a + b
    # no merge_panes / psum / zero_overflow / payload_vectors

register_accumulator(HalfKind())
"""

EDG003_CLEAN = """
from repro.core.estimators import Accumulator, register_accumulator

class FullKind(Accumulator):
    kind = "full"
    def accumulate(self, values, stratum_idx, mask, num_slots, counts=None):
        return values
    def merge(self, a, b):
        return a + b
    def merge_panes(self, stacked):
        return stacked.sum(0)
    def psum(self, state, axis_names, shared=None):
        return state
    def zero_overflow(self, state):
        return state
    def payload_vectors(self):
        return 1
    def payload_flatten(self, state):
        return (("v", state, True, 0.0),)
    def payload_unflatten(self, rows):
        return rows["v"]
    def interval(self, state, n, confidence):
        return (0.0, 0.0)

class Derived(FullKind):
    kind = "derived"  # inherits the full surface: still complete

register_accumulator(FullKind())
register_accumulator(Derived())
"""


def test_edg003_fires_on_partial_accumulator(tmp_path):
    res = lint_tree(tmp_path, {"src/repro/core/plugin.py": EDG003_BAD})
    found = [f for f in res.findings if f.code == "EDG003"]
    assert len(found) == 1
    for missing in (
        "merge_panes",
        "psum",
        "zero_overflow",
        "payload_vectors",
        "payload_flatten",
        "payload_unflatten",
    ):
        assert missing in found[0].message


def test_edg003_clean_on_full_and_inherited_surfaces(tmp_path):
    res = lint_tree(tmp_path, {"src/repro/core/plugin.py": EDG003_CLEAN})
    assert "EDG003" not in codes(res)


# ---------------------------------------------------------------------------
# EDG004 — kernel-triad contract
# ---------------------------------------------------------------------------

KERNEL_OPS = """
def fused_reduce(stratum_idx, values, mask, num_slots, interpret=None):
    return stratum_idx
"""

KERNEL_REF_OK = """
def fused_reduce_ref(stratum_idx, values, mask, num_slots):
    return stratum_idx
"""

KERNEL_REF_DRIFTED = """
def fused_reduce_ref(stratum_idx, values, num_slots):
    return stratum_idx
"""


def test_edg004_fires_on_missing_ref_and_signature_drift(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/kernels/noref/__init__.py": "",
            "src/repro/kernels/noref/ops.py": KERNEL_OPS,
            "src/repro/kernels/drift/__init__.py": "",
            "src/repro/kernels/drift/ops.py": KERNEL_OPS,
            "src/repro/kernels/drift/ref.py": KERNEL_REF_DRIFTED,
        },
    )
    found = [f for f in res.findings if f.code == "EDG004"]
    assert any("no ref.py" in f.message for f in found)
    assert any("required params" in f.message for f in found)


def test_edg004_fires_on_non_f32_accumulation_dtype(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/kernels/lowp/__init__.py": "",
            "src/repro/kernels/lowp/ops.py": KERNEL_OPS,
            "src/repro/kernels/lowp/ref.py": KERNEL_REF_OK,
            "src/repro/kernels/lowp/lowp.py": (
                "import jax.numpy as jnp\n"
                "def k(x):\n"
                "    return jnp.zeros((8,), jnp.float16) + x\n"
            ),
        },
    )
    assert any(
        f.code == "EDG004" and "float16" in f.message for f in res.findings
    )


# Megakernel-shaped triad: many keyword-only mode/layout params with
# defaults (sidx / lat / lon / codes / ext_idx / sk_idx) around a short
# required prefix — the shape PR 8's fused kernel actually ships.
MEGA_OPS = """
def edge_mega(vals, ok, scores, thresholds, num_slots, *,
              sidx=None, lat=None, lon=None, codes=None, precision=None,
              ext_idx=(), sk_idx=(), interpret=None):
    return vals
"""

MEGA_REF_OK = """
import numpy as np

def edge_mega_ref(vals, ok, scores, thresholds, num_slots, *,
                  sidx=None, lat=None, lon=None, codes=None, precision=None,
                  ext_idx=(), sk_idx=()):
    return np.asarray(vals)
"""

MEGA_REF_DRIFTED = """
import numpy as np

def edge_mega_ref(vals, ok, thresholds, num_slots):
    return np.asarray(vals)
"""


def test_edg004_megakernel_shaped_bad_triad(tmp_path):
    """Drifted required prefix fires; a bf16 *accumulator* literal in the
    kernel body fires (staging is the caller's dtype choice, accumulation
    must stay f32)."""
    res = lint_tree(
        tmp_path,
        {
            "src/repro/kernels/mega/__init__.py": "",
            "src/repro/kernels/mega/ops.py": MEGA_OPS,
            "src/repro/kernels/mega/ref.py": MEGA_REF_DRIFTED,
            "src/repro/kernels/mega/mega.py": (
                "import jax.numpy as jnp\n"
                "def k(rows, member):\n"
                "    acc = jnp.zeros((8, 8), jnp.bfloat16)\n"
                "    return acc + rows @ member\n"
            ),
        },
    )
    found = [f for f in res.findings if f.code == "EDG004"]
    assert any("required params" in f.message for f in found)
    assert any("bfloat16" in f.message for f in found)


def test_edg004_edg006_clean_on_megakernel_shaped_triad(tmp_path):
    """The clean twin: keyword-only optional mode params do not count as
    drift, and a numpy oracle with its own encoder helpers is EDG006-pure."""
    res = lint_tree(
        tmp_path,
        {
            "src/repro/kernels/mega/__init__.py": "",
            "src/repro/kernels/mega/ops.py": MEGA_OPS,
            "src/repro/kernels/mega/ref.py": MEGA_REF_OK,
            "src/repro/kernels/mega/mega.py": (
                "import jax.numpy as jnp\n"
                "def k(rows, member):\n"
                "    # staging cast: inputs may arrive reduced, math is f32\n"
                "    return rows.astype(jnp.float32) @ member\n"
            ),
        },
    )
    assert "EDG004" not in codes(res)
    assert "EDG006" not in codes(res)


def test_edg004_clean_on_matching_triad(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/kernels/good/__init__.py": "",
            "src/repro/kernels/good/ops.py": KERNEL_OPS,
            "src/repro/kernels/good/ref.py": KERNEL_REF_OK,
        },
    )
    assert "EDG004" not in codes(res)


# ---------------------------------------------------------------------------
# EDG005 — collective-axis consistency
# ---------------------------------------------------------------------------

SHARDING_DECL = 'MESH_AXIS_NAMES = ("pod", "data", "model")\n'


def test_edg005_fires_on_undeclared_axis_literal(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/sharding/__init__.py": SHARDING_DECL,
            "src/repro/core/reduce.py": (
                "import jax\n"
                "def combine(x):\n"
                '    return jax.lax.psum(x, "modle")\n'  # typo'd axis
            ),
        },
    )
    found = [f for f in res.findings if f.code == "EDG005"]
    assert len(found) == 1 and "'modle'" in found[0].message


def test_edg005_clean_on_declared_axes_and_threaded_axis_vars(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/sharding/__init__.py": SHARDING_DECL,
            "src/repro/core/reduce.py": (
                "import jax\n"
                "def combine(x, axes):\n"
                '    a = jax.lax.psum(x, "data")\n'
                '    b = jax.lax.pmax(x, ("pod", "data"))\n'
                "    return jax.lax.psum(a + b, axes)\n"  # variable: out of scope
            ),
        },
    )
    assert "EDG005" not in codes(res)


def test_edg005_fires_when_sharding_declares_no_vocabulary(tmp_path):
    res = lint_tree(
        tmp_path,
        {"src/repro/sharding/__init__.py": "rules = {}\n"},
    )
    assert any(
        f.code == "EDG005" and "MESH_AXIS_NAMES" in f.message for f in res.findings
    )


def test_edg005_real_tree_declares_axes_in_sharding_compat():
    from tools.edgelint.framework import load_project
    from tools.edgelint.rules.collectives import declared_axes

    axes, where = declared_axes(load_project(REPO_ROOT, ["src"]))
    assert where == "src/repro/sharding/compat.py"
    assert axes == {"pod", "data", "model"}


# ---------------------------------------------------------------------------
# EDG006 — ref purity (oracles are jax-free, self-contained numpy)
# ---------------------------------------------------------------------------


def test_edg006_fires_on_jax_import_in_ref(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/kernels/jaxy/__init__.py": "",
            "src/repro/kernels/jaxy/ops.py": KERNEL_OPS,
            "src/repro/kernels/jaxy/ref.py": (
                "import jax\n"
                "import jax.numpy as jnp\n"
                "def fused_reduce_ref(stratum_idx, values, mask, num_slots):\n"
                "    return jnp.asarray(stratum_idx)\n"
            ),
        },
    )
    found = [f for f in res.findings if f.code == "EDG006"]
    assert len(found) == 2  # one per jax import line
    assert all("jax-free" in f.message for f in found)


def test_edg006_fires_on_relative_and_in_repo_imports(tmp_path):
    res = lint_tree(
        tmp_path,
        {
            "src/repro/kernels/deleg/__init__.py": "",
            "src/repro/kernels/deleg/ops.py": KERNEL_OPS,
            "src/repro/kernels/deleg/ref.py": (
                "from ...core import geohash as _g\n"
                "import repro.core.estimators\n"
                "def fused_reduce_ref(stratum_idx, values, mask, num_slots):\n"
                "    return _g.encode(values, values, 5)\n"
            ),
        },
    )
    found = [f for f in res.findings if f.code == "EDG006"]
    assert any("relative import" in f.message for f in found)
    assert any("in-repo import" in f.message for f in found)


def test_edg006_clean_on_numpy_ref_and_non_ref_jax(tmp_path):
    """numpy/ml_dtypes/stdlib refs pass; jax in ops.py is not EDG006's business."""
    res = lint_tree(
        tmp_path,
        {
            "src/repro/kernels/pure/__init__.py": "",
            "src/repro/kernels/pure/ops.py": "import jax\n" + KERNEL_OPS,
            "src/repro/kernels/pure/ref.py": (
                "from __future__ import annotations\n"
                "import math\n"
                "import numpy as np\n"
                "import ml_dtypes\n"
                "def fused_reduce_ref(stratum_idx, values, mask, num_slots):\n"
                "    return np.asarray(stratum_idx) * math.pi\n"
            ),
            # a ref.py outside kernels/ is out of scope too
            "src/repro/core/ref.py": "import jax\n",
        },
    )
    assert "EDG006" not in codes(res)


# ---------------------------------------------------------------------------
# The production contract: the real tree is clean, suppressions bounded
# ---------------------------------------------------------------------------


def test_megakernel_triad_lints_clean_unsuppressed():
    """PR 8 acceptance: the fused megakernel triad passes edgelint with no
    findings AND no suppression comments anywhere in its directory."""
    res = lint_paths(["src/repro/kernels/edge_megakernel"], root=REPO_ROOT)
    assert res.errors == []
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
    assert res.suppressed == [], [s.render() for s in res.suppressed]


def test_real_tree_is_clean_with_bounded_suppressions():
    res = lint_paths(["src", "tests", "benchmarks"], root=REPO_ROOT)
    assert res.errors == []
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
    # every escape hatch is rare, deliberate, and documents why
    assert 0 < len(res.suppressed) <= 12
    assert all(s.suppress_reason for s in res.suppressed)


def test_cli_json_contract(tmp_path):
    """The CI job's exact interface: JSON output, exit 1 on a violation
    (a reintroduced np.random in src/repro/core), exit 0 once fixed."""
    bad = tmp_path / "src" / "repro" / "core" / "regress.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import numpy as np\nx = np.random.rand(3)\n")
    env = {**os.environ, "PYTHONPATH": str(REPO_ROOT)}
    proc = subprocess.run(
        [sys.executable, "-m", "tools.edgelint", "--format=json", "--root", str(tmp_path), "src"],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        env=env,
    )
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["counts"].get("EDG001") == 1
    assert payload["findings"][0]["path"] == "src/repro/core/regress.py"

    bad.write_text("import jax\ndef f(key):\n    return jax.random.uniform(key, (3,))\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.edgelint", "--format=json", "--root", str(tmp_path), "src"],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["n_findings"] == 0
