"""The measured GNN path must not load the LM substrate.

Training (`launch/elastic_gnn.py`), serving, the engine and the ring
mesh run every benchmark cell; `repro.nn`, `repro.configs` and the LM
launch tools run none.  Importing the first must not import the second,
so that the substrate can be removed as a directory.
"""
import os
import subprocess
import sys
from pathlib import Path

_SRC = Path(__file__).resolve().parents[1] / "src"

_GNN_MODULES = (
    "repro.launch.elastic_gnn",
    "repro.training.train_lib",
    "repro.core.engn",
    "repro.core.models",
    "repro.serving",
    "repro.distributed.sharding",
    "repro.kernels.rer_gather.ops",
)

_LM_PREFIXES = ("repro.nn", "repro.configs")
_LM_TOOLS = tuple(f"repro.launch.{m}"
                  for m in ("specs", "dryrun", "analysis", "jaxpr_cost"))

_PROBE = f"""
import importlib, sys
for name in {_GNN_MODULES!r}:
    importlib.import_module(name)
print("\\n".join(sorted(m for m in sys.modules if m.startswith("repro"))))
"""


def _is_lm(module: str) -> bool:
    return (module in _LM_TOOLS
            or any(module == p or module.startswith(p + ".")
                   for p in _LM_PREFIXES))


def test_gnn_path_imports_no_lm_substrate():
    env = dict(os.environ, PYTHONPATH=str(_SRC), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    loaded = r.stdout.split()
    assert set(_GNN_MODULES) <= set(loaded)
    assert [m for m in loaded if _is_lm(m)] == []
