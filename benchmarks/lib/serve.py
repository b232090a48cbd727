"""The served program, started through its own entry point, with
observer threads beside it: `python benchmarks/lib/serve.py <memstats
file> <arguments of python -m pingoo_tpu ...>`.

Only the process that holds the chip can read its memory or trace it,
and the program exposes no such reading, so this wrapper runs
`pingoo_tpu.__main__` unchanged and writes `memory_stats()` of every
local device to the named file once a second, from the moment the
program itself has initialised the backend (the thread never does);
a second thread takes a bounded profiler trace when the harness asks.
"""

import json
import os
import runpy
import sys
import threading
import time


def _write_memstats(path: str) -> None:
    while True:
        time.sleep(1.0)
        # Never import here: the program may be in the middle of its
        # own `import jax`. Look only at what it has finished loading.
        bridge = sys.modules.get("jax._src.xla_bridge")
        initialised = getattr(bridge, "backends_are_initialized", None)
        try:
            if initialised is None or not initialised():
                continue
            jax = sys.modules["jax"]
            stats = [d.memory_stats() or {} for d in jax.local_devices()]
            doc = {"at_mono": time.monotonic(),
                   "peak_bytes_in_use": [s.get("peak_bytes_in_use")
                                         for s in stats],
                   "bytes_in_use": [s.get("bytes_in_use") for s in stats],
                   "bytes_limit": [s.get("bytes_limit") for s in stats]}
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        except Exception as exc:  # an observer never takes the server down
            print(f"memstats: {exc!r}", file=sys.stderr, flush=True)


def _trace_on_request(path: str) -> None:
    """A bounded profiler window on request: when `<path>` appears,
    holding {"dir", "seconds"}, trace for that long from this thread
    (the session is the process's, whichever thread starts it), then
    write `<path>.done` with how long starting and stopping took. The
    Python tracer stays off: it slows the sidecar's loop severalfold
    and stretches the trace past the seconds the device was traced."""
    while True:
        time.sleep(0.05)
        if not os.path.exists(path):
            continue
        result = {}
        try:
            with open(path, encoding="utf-8") as f:
                req = json.load(f)
            os.remove(path)
            jax = sys.modules["jax"]
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            t0 = time.monotonic()
            jax.profiler.start_trace(req["dir"], profiler_options=options)
            t1 = time.monotonic()
            time.sleep(float(req["seconds"]))
            t2 = time.monotonic()
            result = {"dir": req["dir"], "started_mono": t0,
                      "start_s": t1 - t0, "traced_s": t2 - t1}
            jax.profiler.stop_trace()
            result.update(stopped_mono=time.monotonic(),
                          stop_s=time.monotonic() - t2)
        except Exception as exc:
            result["error"] = repr(exc)
        print(f"trace: {result}", file=sys.stderr, flush=True)
        with open(path + ".done", "w", encoding="utf-8") as f:
            json.dump(result, f)


def main() -> None:
    memstats_path = sys.argv[1]
    sys.argv = ["pingoo_tpu"] + sys.argv[2:]
    # the checkout's root: the working directory is the run's own, so
    # that nothing the program leaves there reaches another run
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    threading.Thread(target=_write_memstats, args=(memstats_path,),
                     daemon=True).start()
    threading.Thread(target=_trace_on_request,
                     args=(memstats_path + ".trace",), daemon=True).start()
    runpy.run_module("pingoo_tpu", run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
