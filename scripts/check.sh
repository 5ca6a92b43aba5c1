#!/usr/bin/env bash
# Fast tier-1 subset: everything except the slow (subprocess / convergence)
# tests. Full suite: PYTHONPATH=src python -m pytest -q
set -euo pipefail
cd "$(dirname "$0")/.."

# Preflight: a broken/missing jax install otherwise surfaces as a wall of
# pytest collection errors. Fail loudly with the actual import error instead.
if ! python -c "import jax" 2>/tmp/jax_import_err.$$; then
  cat /tmp/jax_import_err.$$ >&2
  rm -f /tmp/jax_import_err.$$
  echo "" >&2
  echo "FATAL: 'import jax' failed (see traceback above)." >&2
  echo "Install the pinned deps first, e.g.:" >&2
  echo "    pip install \"jax[cpu]==0.9.0\" \"numpy==2.0.2\" pytest hypothesis" >&2
  exit 1
fi
rm -f /tmp/jax_import_err.$$

# Preflight: trace-level proof that the split-phase overlap schedule issues
# every boundary collective between the phase kernels, on both backends.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.launch.check_schedule

# Preflight: public-API docstrings + README/docs cross-links (stdlib-only;
# the CI docs job additionally runs the pinned ruff's pydocstyle subset).
python scripts/check_docs.py

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m "not slow" "$@"
