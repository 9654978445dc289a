#!/usr/bin/env python3
"""Records ``bench/testdata/round_tiny.xplane.pb``, the trace the reducer's
test reads: on a TPU, two federated rounds of the tiny cell
(``bench/tests/tiny.py``) inside the harness's window and step annotations.

    python3 bench/tests/record_trace.py [out.xplane.pb]    # on a machine with a TPU
    python3 bench/tests/record_trace.py --prune raw.xplane.pb [out.xplane.pb]

The recording is pruned to what ``bench/lib/xplane.py`` reads, so that it
stays well under 1 MB: the device planes' ``XLA Ops``, ``Async XLA Ops`` and
``XLA Modules`` lines and the host's ``bench.`` annotations, with every
event's name and times and none of the profiler's statistics.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

DEFAULT_OUT = REPO / "bench" / "testdata" / "round_tiny.xplane.pb"
DEVICE_LINES = ("XLA Ops", "Async XLA Ops", "XLA Modules")


def prune(src: Path, dst: Path) -> int:
    """Writes ``src`` without what the reduction does not read; returns the
    size written."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from bench.lib import xplane

    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(src).read_bytes())
    planes = []
    for p in space.planes:
        if p.name.startswith("/device:"):
            lines = [ln for ln in p.lines if ln.name in DEVICE_LINES]
        elif p.name.startswith("/host:CPU"):
            for ln in p.lines:
                keep = [e for e in ln.events
                        if p.event_metadata[e.metadata_id].name.startswith(xplane.ANNOTATION_PREFIX)]
                del ln.events[:]
                ln.events.extend(keep)
            lines = [ln for ln in p.lines if len(ln.events)]
        else:
            continue
        del p.lines[:]
        p.lines.extend(lines)
        used = {e.metadata_id for ln in p.lines for e in ln.events}
        for k in list(p.event_metadata):
            if k in used:
                del p.event_metadata[k].stats[:]
            else:
                del p.event_metadata[k]
        for ln in p.lines:
            for e in ln.events:
                del e.stats[:]
        p.stat_metadata.clear()
        del p.stats[:]
        planes.append(p)
    del space.planes[:]
    space.planes.extend(planes)
    data = space.SerializeToString()
    Path(dst).parent.mkdir(parents=True, exist_ok=True)
    Path(dst).write_bytes(data)
    return len(data)


def record(dst: Path) -> int:
    from bench import run
    from bench.lib import spec, xplane
    from bench.tests import tiny

    root = tiny.make_root(Path(tempfile.mkdtemp(prefix="bench-tiny-")))
    sys.path.insert(0, str(REPO / "src"))
    jax = run.start_jax(REPO)
    if jax.devices()[0].platform != "tpu":
        return run.fail("records a TPU trace; JAX found no TPU")
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, tiny.CELL)
    config = spec.load_config(root, bench, cell["config"])
    traffic = spec.load_traffic(root, cell["traffic"])
    job = spec.load_job(root, traffic["kind"]).Job(
        config=config, traffic=traffic, seed=5, reference=spec.load_reference(root, "dense_decoder"),
        chips=1)
    job.setup()
    out = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    jax.profiler.start_trace(str(out), profiler_options=run.profile_options(jax))
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.rounds"):
                job.step()
    jax.profiler.stop_trace()
    src = glob.glob(str(out / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    print(f"{dst}: {prune(Path(src), dst)} bytes")
    shutil.rmtree(out, ignore_errors=True)
    shutil.rmtree(root, ignore_errors=True)
    return 0


def main(argv) -> int:
    if argv[:1] == ["--prune"]:
        dst = Path(argv[2]) if len(argv) > 2 else DEFAULT_OUT
        print(f"{dst}: {prune(Path(argv[1]), dst)} bytes")
        return 0
    return record(Path(argv[0]) if argv else DEFAULT_OUT)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
