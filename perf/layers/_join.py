"""What the join's device programs cost the traced window: the summed device
seconds of the ``XLA Modules`` events named ``jit_join_*`` (the probes, the
probe tables, the expansion, the per-batch counts) and ``jit__build_finish``
(the probe table's gathers after its sort; ``docs/observability.md``).

``obs["trace"]`` keeps the five longest programs only, so the trace file is
read again here, as ``_holistic.py`` reads it: the deployment has written it
under its own temporary directory (``deployments.py``: ``perf-<kind>-*/trace``)
and removes it after the readers have run. Read once a process; ``None``
where there is no file or no such program ran."""

import glob
import os
import re
import tempfile

JOIN = re.compile(r"^jit_(join_|_build_finish)")
_seen: dict = {}


def device_seconds(obs):
    t = obs["trace"]
    if not t or not t["busy_s"] or not t["queries"]:
        return None
    found = glob.glob(os.path.join(tempfile.gettempdir(), "perf-*", "trace",
                                   "**", "*.xplane.pb"), recursive=True)
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    if path not in _seen:
        import reduce_trace

        per_device = [
            sum(d for _, d, name in events if JOIN.match(name))
            for plane, lines in reduce_trace.read_planes(path)
            if reduce_trace.DEVICE_PLANE.match(plane)
            for line, events in lines if line == "XLA Modules"
        ]
        ran = [ns for ns in per_device if ns]
        _seen[path] = sum(ran) / len(ran) / 1e9 if ran else None
    return _seen[path]
