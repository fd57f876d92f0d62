"""Writes ``program_spans.xplane.pb``: a constructed trace with nested
program spans, two modules' runs on the device and their HLO (the
``op_name`` of each instruction, as a TPU trace keeps it), whose known
answers ``bench/tests/test_program_trace.py`` asserts.

    python bench/tests/data/make_program_spans.py

needs the XPlane protocol buffers of an installed TensorFlow; the tests
read the committed file with JAX alone. Times are in nanoseconds.
"""

import os

from tensorflow.compiler.xla.service import hlo_pb2
from tensorflow.tsl.profiler.protobuf import xplane_pb2

HERE = os.path.dirname(os.path.abspath(__file__))

# (text, start, end); the running module (below) gives each its op_name
DEVICE_OPS = [
    ("%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
     100, 420),
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 100, 300),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop", 250, 400),
    ('%decision_fused.3 = f32[8]{0} custom-call(f32[8]{0} %a), '
     'custom_call_target="tpu_custom_call"', 600, 800),
    ("%fusion.4 = f32[] fusion(f32[8]{0} %c), kind=kInput", 1500, 1600),
    ("%copy.5 = f32[8]{0} copy(f32[8]{0} %d)", 1900, 1950),
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %d), kind=kLoop", 1950, 2100),
]

# the XLA Modules line: (name, start, end)
MODULE_RUNS = [("jit_run(42)", 90, 1700), ("jit_other(7)", 1850, 2100)]

# per module: computation -> [(instruction, op_name)]
MODULES = {
    "jit_run(42)": {
        "main": [("p.0", ""), ("while.1", "jit(run)/while"),
                 ("fusion.4", "jit(run)/fl.eval/reduce_sum")],
        "body": [("fusion.1", "jit(run)/while/body/fl.decision/mul"),
                 ("fusion.2",
                  "jit(run)/while/body/fl.update/fl.aggregate/add"),
                 ("decision_fused.3",
                  "jit(run)/while/body/fl.decision/pallas_call")],
    },
    "jit_other(7)": {
        "main": [("copy.5", "jit(other)/copy"),
                 ("fusion.1", "jit(other)/fl.eval/mul")],
    },
}

# per host thread: (name, start, end, flush ordinal or None)
HOST_THREADS = {
    "python": [
        ("bench.window", 0, 2000, None),
        ("submit", 0, 55, None),
        ("service.submit", 10, 40, 4),
        ("flush", 55, 1950, None),
        ("service.flush", 60, 1900, 4),
        ("service.stage", 70, 90, 4),
        ("service.dispatch", 90, 120, 4),
        ("service.pull#flush=4#", 450, 550, None),
        ("service.pull", 550, 700, 4),
        ("service.unpack", 700, 900, 4),
        ("service.pull", 1950, 2100, 5),
    ],
    "worker": [
        ("fl.dispatch", -100, 50, None),
        ("fl.dispatch", 1000, 1400, None),
    ],
}

T0 = 1000   # line timestamps, so that no event starts before its line


def build() -> xplane_pb2.XSpace:
    space = xplane_pb2.XSpace()
    meta_ids = {}

    def metadata(plane, name):
        key = (plane.name, name)
        if key not in meta_ids:
            meta_ids[key] = len(plane.event_metadata) + 1
            plane.event_metadata[meta_ids[key]].id = meta_ids[key]
            plane.event_metadata[meta_ids[key]].name = name
        return meta_ids[key]

    def stat_id(plane, name):
        for i, m in plane.stat_metadata.items():
            if m.name == name:
                return i
        i = len(plane.stat_metadata) + 1
        plane.stat_metadata[i].id = i
        plane.stat_metadata[i].name = name
        return i

    def event(plane, line, name, start, end):
        ev = line.events.add()
        ev.metadata_id = metadata(plane, name)
        ev.offset_ps = (start + T0) * 1000
        ev.duration_ps = (end - start) * 1000
        return ev

    dev = space.planes.add()
    dev.id, dev.name = 1, "/device:TPU:0"
    ops = dev.lines.add()
    ops.id, ops.name, ops.timestamp_ns = 1, "XLA Ops", -T0
    for text, start, end in DEVICE_OPS:
        event(dev, ops, text, start, end)
    mods = dev.lines.add()
    mods.id, mods.name, mods.timestamp_ns = 2, "XLA Modules", -T0
    for name, start, end in MODULE_RUNS:
        event(dev, mods, name, start, end)
    meta = space.planes.add()
    meta.id, meta.name = 3, "/host:metadata"
    for name, comps in MODULES.items():
        proto = hlo_pb2.HloProto()
        for comp_name, insts in comps.items():
            comp = proto.hlo_module.computations.add()
            comp.name = comp_name
            for inst_name, op_name in insts:
                inst = comp.instructions.add()
                inst.name = inst_name
                inst.metadata.op_name = op_name
        st = meta.event_metadata[metadata(meta, name)].stats.add()
        st.metadata_id = stat_id(meta, "Hlo Proto")
        st.bytes_value = proto.SerializeToString()
    host = space.planes.add()
    host.id, host.name = 2, "/host:CPU"
    for k, (thread, spans) in enumerate(HOST_THREADS.items()):
        line = host.lines.add()
        line.id, line.name, line.timestamp_ns = k + 1, thread, -T0
        for name, start, end, flush in spans:
            ev = event(host, line, name, start, end)
            if flush is not None:
                st = ev.stats.add()
                st.metadata_id = stat_id(host, "flush")
                st.int64_value = flush
    return space


if __name__ == "__main__":
    with open(os.path.join(HERE, "program_spans.xplane.pb"), "wb") as f:
        f.write(build().SerializeToString())
