"""Shared benchmark utilities: timing, CSV + JSON output."""
from __future__ import annotations

import json
import pathlib
import platform
import time
from typing import Callable, Optional

import jax

#: repo root — BENCH_<name>.json files land here so the perf trajectory
#: is collected at a fixed, greppable location across PRs.
REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def time_solve(fn: Callable, *args, repeats: int = 3, **kw):
    """Median wall time of fn(*args) with device sync."""
    best = []
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(getattr(out, "x", out))
        best.append(time.perf_counter() - t0)
    best.sort()
    return out, best[len(best) // 2]


def emit(rows, header):
    print(",".join(header))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in header))
    return rows


def write_bench_json(name: str, rows, meta: Optional[dict] = None) -> str:
    """Persist one benchmark section as ``BENCH_<name>.json`` (repo root).

    The payload is self-describing: rows as emitted, plus enough context
    (backend, host, timestamp) to compare runs across machines and PRs.
    Returns the path written.
    """
    payload = {
        "name": name,
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "host": {
            "platform": platform.platform(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "default_backend": jax.default_backend(),
        },
        "meta": meta or {},
        "rows": rows,
    }
    path = REPO_ROOT / f"BENCH_{name}.json"
    path.write_text(json.dumps(payload, indent=2, default=str) + "\n")
    print(f"# wrote {path}")
    return str(path)
