"""The device child: the only process of a run that imports jax.

binder dispatches nothing to the accelerator (PERF.md section 3), so what
this child can say about the device is: which chip the run's machine holds
(the run fails without one), that the one piece of JAX code the repository
owns, ``__graft_entry__.entry()``, runs on it and agrees with NumPy (an
installation check, copied from ``chip_smoke.py``), the device's peak
memory, and, in a traced run, how long the device was busy: the profiler
is started before the installation check and stopped when the window has
closed, so the trace holds every device operation of the run (the check's,
which a run that is not traced makes too, and nothing made for the trace)
and the idle share is measured, not assumed.

Protocol: the harness starts ``device.py <root> <cpu|tpu> <trace 0|1>``;
the child prints one JSON line (the device) and, when traced, waits for a
``stop`` line on stdin and prints a second JSON line (busy seconds, the
traced seconds, the device operations by time).
"""
import glob
import json
import os
import shutil
import sys
import time


def fail(why: str) -> None:
    sys.exit(f"benchmark device child: FAILED: {why}")


def reduce_trace(trace_dir: str) -> dict:
    """Busy seconds of the device in a profiler trace: the union of the
    intervals in which an operation ran, averaged over the device planes;
    and the operations that took most time."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        fail(f"the profiler wrote no trace under {trace_dir}")
    busy, by_name = [], {}
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
        spans = []
        for line in ops:
            for ev in line.events:
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                # an op's name is its whole HLO line: the part before
                # " = " names it
                name = ev.name.split(" = ")[0]
                by_name[name] = by_name.get(name, 0.0) \
                    + ev.duration_ns / 1e9
        union, end = 0.0, None
        for start, stop in sorted(spans):
            if end is None or start > end:
                union += stop - start
                end = stop
            elif stop > end:
                union += stop - end
                end = stop
        busy.append(union / 1e9)
    if not busy:
        return {"busy_s": None, "device_ops": []}
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / len(busy),
            "device_ops": [[name, seconds] for name, seconds in top]}


def main() -> None:
    root, want, traced = sys.argv[1], sys.argv[2], sys.argv[3] == "1"
    sys.path.insert(0, root)
    import numpy as np
    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != want:
        fail(f"jax.devices()[0].platform is {devices[0].platform!r}, need "
             f"{want!r}: no accelerator (--cpu is the rehearsal mode)")
    from __graft_entry__ import entry

    trace_dir = os.path.join(root, "benchmark", "out", "device_trace")
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)    # one trace, this run's
        jax.profiler.start_trace(trace_dir)
        t0 = time.monotonic()
    fn, args = entry()
    out = jax.block_until_ready(jax.jit(fn)(*args))
    got = [float(o) for o in out]
    ref = [float(np.mean(args[0])), float(np.percentile(args[0], 50.0)),
           float(np.percentile(args[0], 99.0))]
    if not np.allclose(got, ref, rtol=1e-4):
        fail(f"entry() aggregation {got} != numpy {ref}")

    def memory_peak() -> int:
        stats = devices[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    print(json.dumps({"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": memory_peak()}), flush=True)
    if not traced:
        return
    if sys.stdin.readline().strip() != "stop":
        fail("expected 'stop'")
    window_s = time.monotonic() - t0
    jax.profiler.stop_trace()
    out = reduce_trace(trace_dir)
    out.update(window_s=window_s, memory_peak_bytes=memory_peak())
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
