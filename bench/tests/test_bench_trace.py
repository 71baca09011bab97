"""The trace reduction: on events made by hand, and on a small trace
recorded on one TPU v5e (``data/tiny-colo.xplane.pb.gz``, the tiny test cell
served on the chip by ``record_trace.py``). Reading the file needs only
JAX's profiler data reader; nothing here loads the TPU library."""
import gzip
import os

import benchtest
from benchkit import tracing as tr

E = tr.Event


def _trace():
    host = [E("bench.traced", 0, 1000),
            E("bench.step", 0, 400), E("bench.call", 10, 60,
                                       {"tenant": "ls:x", "kind": "decode"}),
            E("bench.idle", 500, 900)]
    modules = [E("jit__decode_paged", 50, 300), E("jit_argmax", 300, 320),
               E("jit__chunk_paged", 600, 700)]
    ops = [E("%fusion.1 = bf16[6,1,2048] fusion(...)", 50, 100),
           E("%decode_attention_paged.3 = bf16[6,8,2,128] custom-call(...),"
             " custom_call_target=\"tpu_custom_call\"", 100, 200),
           E("%fusion.2 = f32[6] fusion(...)", 150, 300),
           E("%reduce = s32[6] reduce(...)", 300, 320),
           E("%prefill_attention_paged.6 = bf16[6,8,512,128] custom-call("
             "...), custom_call_target=\"tpu_custom_call\"", 600, 650),
           E("%fusion.3 = bf16[6,64,2048] fusion(...)", 650, 700),
           E("%stray = f32[] add(...)", 1200, 1300)]
    return tr.Trace(host, modules, ops)


def test_busy_is_the_union_inside_the_window():
    t = _trace()
    assert tr.busy_ns(t, 0, 1000) == (320 - 50) + (700 - 600)
    assert tr.busy_ns(t, 0, 200) == 150


def test_idle_gaps_by_host_span():
    gaps = tr.idle_gaps(_trace(), 0, 1000)
    # busy [50, 320) and [600, 700); each gap goes to the innermost span
    # around its middle
    assert gaps == {"call ls:x/decode": 50, "outside harness spans": 280,
                    "idle": 300}


def test_step_programs_and_their_kernels():
    t = _trace()
    mods = tr.step_modules(t, 0, 1000)
    assert [m.name for m in mods] == ["jit__decode_paged",
                                      "jit__chunk_paged"]
    per = tr.per_module(t, mods, ("attention_paged",))
    assert [p["device_ns"] for p in per] == [250, 100]
    assert [p["kernel_ns"] for p in per] == [100, 50]
    top = tr.top_ops(t, mods, ["ls:x/decode", "be:y/chunk64"], 0, 1000)
    assert top[0] == ["ls:x/decode/fusion.2", 150e-9]
    assert ["other programs/reduce", 20e-9] in top


TINY = os.path.join(benchtest.DATA, "tiny-colo.xplane.pb.gz")


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(TINY) as src:
        path.write_bytes(src.read())
    t = tr.load(path)
    w = tr.window(t)
    assert w is not None and t.n_devices == 1
    lo, hi = w.start, w.end
    busy = tr.busy_ns(t, lo, hi)
    assert 0 < busy < hi - lo
    calls = [e for e in t.host if e.name == "bench.call"
             and lo <= e.start < hi]
    mods = tr.step_modules(t, float("-inf"), float("inf"))
    assert len(mods) >= len(calls) > 0
    per = tr.per_module(t, mods, ("attention_paged",))
    # every step program encloses its attention kernels, once per layer
    assert all(0 < p["kernel_ns"] < p["device_ns"] for p in per)
    kinds = {c.args["kind"] for c in calls}
    assert "decode" in kinds and any(k.startswith("chunk") for k in kinds)
    gaps = tr.idle_gaps(t, lo, hi)
    assert sum(gaps.values()) == hi - lo - busy
    # the layer scan's while loop encloses the ops it runs: not counted
    top = tr.top_ops(t, mods, ["step"] * len(mods), lo, hi)
    assert top and not any("/while" in name for name, _ in top)
    assert any("attention_paged" in name for name, _ in top)
