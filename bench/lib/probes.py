"""Host-side probes: wrap the engine thread's calls into each layer.

A probe file (``bench/probes/<probe>.py``) names one method of the
program (``MODULE``, ``ATTR`` as ``Class.method``) and the annotation
it is recorded under (``NAME``, a ``bench.*`` name), and may define
``capture(*args)`` to note what the call works on. While installed,
each call is timed on the host clock and wrapped in a
``jax.profiler.TraceAnnotation`` of that name, so the device trace's
idle gaps can be attributed to it. A method that no longer exists is
reported by name and its metrics read null; the run goes on.
"""
from __future__ import annotations

import importlib
import importlib.util
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


def load_probe_files(folder: Path) -> List[object]:
    mods = []
    for f in sorted(folder.glob("*.py")):
        spec = importlib.util.spec_from_file_location(
            f"bench_probe_{f.stem}", f)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mods.append(mod)
    return mods


class Recorder:
    """Calls recorded per probe name: (t0, t1, capture) on the host
    clock. ``missing`` maps a probe name to the attribute not found."""

    def __init__(self):
        self.calls: Dict[str, List[tuple]] = {}
        self.missing: Dict[str, str] = {}
        self._undo: List[tuple] = []

    def wrap(self, name: str, owner, meth: str, capture=None) -> None:
        orig = getattr(owner, meth)
        calls = self.calls.setdefault(name, [])

        def probed(*args, **kwargs):
            extra = capture(*args, **kwargs) if capture else None
            t0 = time.perf_counter()
            try:
                with TraceAnnotation(name):
                    return orig(*args, **kwargs)
            finally:
                calls.append((t0, time.perf_counter(), extra))

        probed.__wrapped__ = orig
        setattr(owner, meth, probed)
        self._undo.append((owner, meth, orig))

    def install(self, probes) -> "Recorder":
        for p in probes:
            target = f"{p.MODULE}.{p.ATTR}"
            try:
                owner = importlib.import_module(p.MODULE)
                *path, meth = p.ATTR.split(".")
                for part in path:
                    owner = getattr(owner, part)
                getattr(owner, meth)
            except (ImportError, AttributeError) as e:
                self.missing[p.NAME] = target
                print(f"warning: probe {p.NAME}: {target} not found "
                      f"({e!r}); its metrics read null", file=sys.stderr)
                continue
            self.wrap(p.NAME, owner, meth, getattr(p, "capture", None))
        return self

    def uninstall(self) -> None:
        for owner, meth, orig in reversed(self._undo):
            setattr(owner, meth, orig)
        self._undo.clear()

    def within(self, name: str, lo: float, hi: float) -> Optional[list]:
        """Calls of ``name`` that started inside [lo, hi); None when the
        probe is missing."""
        if name in self.missing or name not in self.calls:
            return None
        return [c for c in self.calls[name] if lo <= c[0] < hi]
