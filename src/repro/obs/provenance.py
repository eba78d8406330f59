"""Run provenance: the environment fingerprint every artifact should carry.

``BENCH_*.json`` files used to hold numbers with no record of what
produced them — useless for cross-machine comparison and for the
selection-corpus training data the ML follow-up (arXiv:2303.05098) needs.
``env_info()`` collects the facts that determine whether two measurements
are comparable: jax version, backend, device kind/count, the
interpret-mode override, and the git revision of the code that ran.
"""
from __future__ import annotations

import os
import platform
import subprocess
import sys
from typing import Optional


def git_rev(cwd: Optional[str] = None) -> Optional[str]:
    """Short git revision of the running checkout (None outside a repo)."""
    if cwd is None:
        cwd = os.path.dirname(os.path.abspath(__file__))
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=cwd, capture_output=True, text=True,
                             timeout=5)
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else None
    except (OSError, subprocess.SubprocessError):
        return None


def env_info() -> dict:
    """Environment/provenance dict embedded in every benchmark artifact.

    Cheap (one cached git subprocess, no device work beyond what import
    already did) and always JSON-serializable; failures degrade to None
    fields, never to an exception.
    """
    import jax

    try:
        devs = jax.devices()
        device_kind = devs[0].device_kind if devs else None
        device_count = len(devs)
    except RuntimeError:
        device_kind, device_count = None, 0
    try:
        from repro.kernels.ops import interpret_mode
        interp = bool(interpret_mode())
    except Exception:  # pragma: no cover - partial installs
        interp = None
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": device_kind,
        "device_count": device_count,
        "interpret_mode": interp,
        "trace_mode": os.environ.get("REPRO_TRACE") or "off",
        "xla_flags": os.environ.get("XLA_FLAGS") or None,
        "git_rev": git_rev(),
        "python": sys.version.split()[0],
        "platform": platform.platform(),
    }
