#!/usr/bin/env python3
"""Run a test-only token configuration (``tests/data/<name>/``) through
``run.run_cell`` on the accelerator, in a scratch copy of this benchmark
to which it is added as new files only, and report what the check holds.

    python3 bench/tests/run_token_config.py --config big-lm \\
        --seed <n> --seconds <s>

The configuration is not a cell of ``BENCHMARK.json``; ``big-lm`` sizes
one client's state at gigabytes, so a check that kept device copies
would not fit beside the program on one 16 GB chip. The last line of
standard output is one JSON object: the run's result, or its ``error``,
and

``check_device_bytes``  bytes of the distinct device arrays that the
                        cell's capture, fold sample and migration sample
                        hold as the check starts, or at the failure;
``live_device_bytes``   bytes of every live device array then (after the
                        program's state was freed, where it started);
``migration_sample_bytes`` host bytes the migration sample holds as the
                        check starts;
``host``                the process's host memory as the cell is built
                        (``built``), after set-up (``setup``), after the
                        window (``window``) and as the check starts, the
                        program's state freed (``check``): its resident
                        set (``rss``), and the bytes glibc's allocator
                        has handed out (``malloc_used``) and holds freed
                        (``malloc_free``), from ``mallinfo2``;
``check_host_peak_rss_bytes`` the process's largest resident set seen
                        while the check ran (sampled every 20 ms);
``process_peak_rss_bytes``    the process's peak resident set.

It needs an accelerator, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import ctypes
import json
import os
import resource
import shutil
import sys
import tempfile
import threading
import traceback
from pathlib import Path

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
for p in (BENCH, BENCH.parent / "src", TESTS):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import cellbase  # noqa: E402
import run  # noqa: E402
from _small import add_token_config  # noqa: E402


class RssPeak:
    """Largest resident set of this process while running, read from
    ``/proc/self/statm`` every 20 ms."""

    def __init__(self):
        self.peak = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    @staticmethod
    def now() -> int:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")

    def _watch(self):
        while not self._stop.is_set():
            self.peak = max(self.peak, self.now())
            self._stop.wait(0.02)

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread.ident is not None:      # it was started
            self._thread.join(timeout=5)


class _MallInfo2(ctypes.Structure):
    _fields_ = [(k, ctypes.c_size_t) for k in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


def host_memory() -> dict:
    """Resident set, and glibc malloc's bytes in use and held free."""
    libc = ctypes.CDLL("libc.so.6")
    libc.mallinfo2.restype = _MallInfo2
    m = libc.mallinfo2()
    return {"rss": RssPeak.now(), "malloc_used": m.uordblks + m.hblkhd,
            "malloc_free": m.fordblks}


def _device_bytes(tree) -> int:
    import jax
    seen = {id(x): x for x in jax.tree.leaves(tree)
            if isinstance(x, jax.Array) and not x.is_deleted()}
    return sum(int(x.nbytes) for x in seen.values())


def main(argv=None) -> int:
    import jax
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cells, out, rss = [], {"host": {}}, RssPeak()
    init, numbers = cellbase.CellBase.__init__, cellbase.CellBase.numbers

    def held(cell):
        return _device_bytes({"capture": cell.capture.streams,
                              "fold": cell.fold, "migs": cell.migs})

    def after(cell, name, stage):
        f = getattr(cell, name)

        def wrapped(*a, **kw):
            r = f(*a, **kw)
            out["host"][stage] = host_memory()
            return r
        setattr(cell, name, wrapped)

    def init_w(self, *a, **kw):
        init(self, *a, **kw)
        cells.append(self)
        out["host"]["built"] = host_memory()
        after(self, "setup", "setup")
        after(self, "window", "window")

    def numbers_w(self):
        out["check_device_bytes"] = held(self)
        out["live_device_bytes"] = _device_bytes(jax.live_arrays())
        out["migration_sample_bytes"] = cellbase._host_bytes(self.migs)
        out["host"]["check"] = host_memory()
        rss.start()
        return numbers(self)
    cellbase.CellBase.__init__ = init_w
    cellbase.CellBase.numbers = numbers_w

    root = Path(tempfile.mkdtemp(prefix="token-config-")) / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    rc = 0
    try:
        workload = add_token_config(root, args.config)
        result = run.run_cell(workload, args.seed, args.seconds, False,
                              root=root)
        out = {**result, **out}
    except Exception as e:       # the report is the point: keep its line
        rc = 1
        traceback.print_exc()
        out["error"] = f"{type(e).__name__}: {str(e)[:2000]}"
        if cells:
            out["check_device_bytes"] = held(cells[-1])
        out["live_device_bytes"] = _device_bytes(jax.live_arrays())
    finally:
        rss.stop()
        shutil.rmtree(root.parent, ignore_errors=True)
    out["check_host_peak_rss_bytes"] = rss.peak
    out["process_peak_rss_bytes"] = \
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
