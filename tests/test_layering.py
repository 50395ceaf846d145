"""The model layer stands below the serving layer and the RMA substrate.

``repro.models`` owns the model stack and the paged KV cache's layout
(``models/paged_cache.py``); ``repro.serve`` builds on it.  Importing the
models in a fresh interpreter must load no ``repro.serve`` or
``repro.core`` module, so the arrows between the layers point one way.
"""
import os
import subprocess
import sys

HERE = os.path.dirname(__file__)


def test_models_import_loads_no_serve_or_core_module():
    code = ("import sys, repro.models; print(sorted(m for m in sys.modules "
            "if m.startswith(('repro.serve', 'repro.core'))))")
    env = dict(os.environ, PYTHONPATH=os.path.join(HERE, "..", "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "[]", proc.stdout
