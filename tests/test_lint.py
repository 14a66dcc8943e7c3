"""Host-sync lint (tools/lint_host_sync.py) gating the jit-pure modules.

The repo check IS the test: any `.item()` / `np.asarray` / `float(traced)`
creeping into ops/, kernels/, parallel/train_step.py, or
observability/health.py fails CI here.  The synthetic cases pin down what
the AST rules catch and what they deliberately allow."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

from lint_host_sync import JIT_PURE, lint_paths, lint_source  # noqa: E402


def test_jit_pure_modules_are_clean():
    findings = lint_paths(str(REPO))
    assert not findings, "host-sync calls in jit-pure modules:\n" + "\n".join(
        str(f) for f in findings
    )


def test_lint_targets_exist():
    for t in JIT_PURE:
        assert (REPO / t).exists(), t


def test_catches_item_call():
    src = "def f(x):\n    return x.item()\n"
    assert [f.rule for f in lint_source(src)] == ["item"]


def test_catches_np_asarray_and_aliases():
    src = (
        "import numpy\n"
        "import numpy as np\n"
        "def f(x):\n"
        "    a = np.asarray(x)\n"
        "    b = numpy.array(x)\n"
        "    return a, b\n"
    )
    assert [f.rule for f in lint_source(src)] == ["np-asarray", "np-asarray"]


def test_allows_numpy_host_array_construction():
    # building new host arrays is not a sync — only asarray/array conversions
    src = "import numpy as np\ndef f(n):\n    return np.tril(np.ones((n, n)))\n"
    assert lint_source(src) == []


def test_catches_device_get_and_block():
    src = (
        "import jax\n"
        "def f(x):\n"
        "    jax.block_until_ready(x)\n"
        "    return jax.device_get(x)\n"
    )
    assert sorted(f.rule for f in lint_source(src)) == ["block_until_ready", "device_get"]


def test_catches_value_casts_but_allows_shape_arithmetic():
    src = (
        "import math\n"
        "def f(x, metrics, thres):\n"
        "    bad1 = float(metrics['loss'])\n"
        "    bad2 = int(x)\n"
        "    ok1 = int((1.0 - thres) * 100)\n"
        "    ok2 = int(x.shape[0])\n"
        "    ok3 = int(math.ceil(thres))\n"
        "    ok4 = float(1e-3)\n"
        "    return bad1, bad2, ok1, ok2, ok3, ok4\n"
    )
    rules = [f.rule for f in lint_source(src)]
    assert rules == ["float-cast", "int-cast"]


def test_waiver_comment_suppresses():
    src = (
        "import numpy as np\n"
        "def f(x):\n"
        "    a = np.asarray(x)  # host-sync-ok: static at trace time\n"
        "    # host-sync-ok (next line operates on a static python float)\n"
        "    b = int(x)\n"
        "    return a, b\n"
    )
    assert lint_source(src) == []


def test_lint_cli_runs_clean(capsys):
    from lint_host_sync import main

    assert main(["--root", str(REPO)]) == 0
    assert "clean" in capsys.readouterr().out


def test_decode_side_does_not_know_scan_layers():
    """`scan_layers` is a property of the training forward
    (`transformer.apply_transformer` / `_apply_scan`).  The cache and pool
    format is `models/transformer.py`'s alone and does not depend on it, so
    no module that serves, samples or quantizes may name the flag."""
    pkg = REPO / "dalle_pytorch_tpu"
    decode_side = sorted((pkg / "serving").glob("*.py")) + [
        pkg / "models" / "speculative.py", pkg / "models" / "sampling.py",
        pkg / "quantization.py", pkg / "api.py",
        pkg / "cli" / "serve.py", pkg / "cli" / "generate.py",
    ]
    assert len(decode_side) > 6
    naming = [str(p.relative_to(REPO)) for p in decode_side if "scan_layers" in p.read_text()]
    assert not naming, f"decode-side modules that name scan_layers: {naming}"
    # ... and inside transformer.py nothing from init_cache down reads it
    src = (pkg / "models" / "transformer.py").read_text()
    assert "scan_layers" not in src[src.index("def init_cache("):]
