"""The program's names in a trace: the op_name reader, the split of the
step by scope and communicator, and the recorded chip traces."""

import collections
import gzip
import json
import os

import pytest

import trace_reduce as tr
import trace_scopes as ts

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")

FUSION = ("%fusion.{} = bf16[16,1024]{{1,0}} fusion(bf16[16,1024]{{1,0}} "
          "%p), kind=kOutput, calls=%fused_computation")
ALLREDUCE = "%all-reduce.{} = bf16[16,1024]{{1,0}} all-reduce(bf16[16,1024] %x)"
LOOP = "%while.2 = (s32[], bf16[16,1024]) while((s32[], bf16[16,1024]) %t)"
TP = "zmpi.coll.tp.perfbench_tp.allreduce"
DP = "zmpi.coll.dp.perfbench_dp.allreduce"


def _events():
    # one device, two steps over 0..1000 ns, as test_trace_reduce's
    host = [["pb.step", 0, 500], ["pb.batch", 0, 10],
            ["pb.dispatch", 10, 40], ["pb.step", 500, 400],
            ["pb.wait", 600, 300], ["pb.fetch", 900, 100]]
    ops = [[LOOP, 50, 450, []],
           [FUSION.format(1), 50, 100, ["zmpi.attn"]],
           [FUSION.format(2), 150, 50, ["zmpi.attn", "zmpi.mlp"]],
           [ALLREDUCE.format(3), 200, 150, ["zmpi.mlp", TP]],
           [FUSION.format(4), 300, 100, ["zmpi.loss"]],
           [FUSION.format(5), 400, 100, []],
           [ALLREDUCE.format(6), 700, 100, [DP]],
           [FUSION.format(7), 800, 50, ["zmpi.optimizer"]]]
    flights = [[ALLREDUCE.format(8), 850, 30, [DP]]]
    return {"devices": {"0": {"ops": ops, "async": flights}}, "host": host}


def test_scopes_strip_jax_name_stack():
    assert ts.scopes("jit(spmd_grads)/shard_map/transpose(jvp(zmpi.loss))"
                     "/while/body/dynamic_slice") == ["zmpi.loss"]
    assert ts.scopes("jit(spmd_grads)/shard_map/transpose(jvp())/while/body"
                     "/closed_call/checkpoint/zmpi.attn/" + TP + "/psum") \
        == ["zmpi.attn", TP]
    # XLA merges the names of ops it folded together: the first one counts
    assert ts.scopes("jit(f)/zmpi.mlp/reshape;zmpi.attn/transpose") \
        == ["zmpi.mlp"]
    assert ts.scopes("jit(batch)/jit(_uniform)/iota_2x32_shape:") == []


def test_split_partitions_leaf_time():
    sp = ts.split(_events(), 1)
    per = 1e-6 / 2  # ns to ms, two steps
    assert sp["attn"] == pytest.approx(100 * per)
    assert sp["mlp"] == pytest.approx(50 * per)      # innermost scope
    assert sp["loss"] == pytest.approx(100 * per)
    assert sp["opt"] == pytest.approx(50 * per)
    assert sp["other"] == pytest.approx(100 * per)
    assert sp["coll.tp"] == pytest.approx(150 * per)
    assert sp["coll.dp"] == pytest.approx(100 * per)
    parts = sum(v for k, v in sp.items()
                if k != "leaf" and not k.startswith("exposed."))
    assert parts == pytest.approx(sp["leaf"]) and sp["leaf"] == \
        pytest.approx(650 * per)
    # tp: 200..300 of its 200..350 runs alone; dp: its op and its flight
    assert sp["exposed.tp"] == pytest.approx(100 * per)
    assert sp["exposed.dp"] == pytest.approx(130 * per)
    red = tr.reduce_events(_events(), 1)
    assert sp["exposed.tp"] + sp["exposed.dp"] == pytest.approx(
        1e3 * red["devices"][0]["coll_exposed_s"] / red["steps"])


def test_readings_none_and_absence():
    got = ts.readings(_events(), 1)
    assert set(got) == set(ts.METRICS) and None not in got.values()
    # a scope that holds no op: its metric reads nothing
    ev = _events()
    for o in ev["devices"]["0"]["ops"]:
        o[3] = [s for s in o[3] if s != "zmpi.loss"]
    got = ts.readings(ev, 1)
    assert got["loss.ms"] is None and got["attn.ms"] is not None
    # a program that names nothing: the metrics are left out
    ev = _events()
    for o in ev["devices"]["0"]["ops"] + ev["devices"]["0"]["async"]:
        o[3] = []
    assert ts.readings(ev, 1) is None


def test_idle_gaps_read_the_innermost_span():
    host = [["pb.step", 0, 200], ["pb.dispatch", 0, 100],
            ["zmpi.train.grad", 0, 40], ["zmpi.train.apply", 40, 60],
            ["pb.wait", 100, 100]]
    ops = [[FUSION.format(i), s, d, []]
           for i, (s, d) in enumerate([(10, 10), (30, 20), (90, 10),
                                       (120, 80)])]
    ev = {"devices": {"0": {"ops": ops, "async": []}}, "host": host}
    gaps = sorted((round(s * 1e9), lab) for lab, s in ts.idle_gaps(ev, 1))
    # a gap during the AdamW dispatch lies as much in pb.dispatch as in
    # the shorter zmpi.train.apply inside it, which names it
    assert gaps == [(10, "zmpi.train.grad"), (10, "zmpi.train.grad"),
                    (20, "pb.wait"), (40, "zmpi.train.apply")]
    assert sorted(lab for lab, _ in tr.reduce_events(ev, 1)["breakdown"]
                  ["idle_gaps"]) == ["pb.dispatch"] * 3 + ["pb.wait"]


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields):
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_names_read_the_event_metadata(tmp_path):
    """A hand-made ``XSpace``: the device plane's event metadata carries
    ``tf_op`` as a string or as a reference to an interned name; a host
    plane's is not read."""
    stat = lambda sid, name: (5, _msg((1, sid), (2, _msg((1, sid),
                                                        (2, name)))))
    event = lambda eid, text, *stats: (4, _msg(
        (1, eid), (2, _msg((1, eid), (2, text), *stats))))
    device = _msg(
        (2, "/device:TPU:0"), stat(7, "tf_op"), stat(8, "program_id"),
        stat(9, "jit(f)/zmpi.attn/dot_general:"),
        event(1, FUSION.format(1), (5, _msg((1, 8), (3, 42))),
              (5, _msg((1, 7), (5, "jit(f)/zmpi.mlp/dot_general:")))),
        event(2, FUSION.format(2), (5, _msg((1, 7), (7, 9)))),
        event(3, FUSION.format(3)))
    host = _msg((2, "/host:CPU"), stat(7, "tf_op"),
                event(1, "x", (5, _msg((1, 7), (5, "jit(g)/y")))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    assert ts.op_names(str(path)) == {"/device:TPU:0": {
        FUSION.format(1): "jit(f)/zmpi.mlp/dot_general:",
        FUSION.format(2): "jit(f)/zmpi.attn/dot_general:"}}


def _load(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


SCOPED = [("gpt2m-train-s1024.scoped.trace.json.gz", 1),
          ("gpt2l-train-dp2tp2.scoped.trace.json.gz", 4)]


@pytest.mark.parametrize("name,chips", SCOPED,
                         ids=["gpt2m-train-s1024", "gpt2l-train-dp2tp2"])
def test_recorded_scoped_trace(name, chips):
    """Traced steps of each cell on a v5e, as ``trace_scopes.py`` keeps
    them (five steps of cell 1; one of cell 2)."""
    ev = _load(name)
    sp = ts.split(ev, chips)
    model = ("attn", "mlp", "loss", "opt", "other")
    assert all(sp[k] > 0 for k in model)
    coll = sum(v for k, v in sp.items() if k.startswith("coll."))
    assert sum(sp[k] for k in model) + coll == pytest.approx(sp["leaf"])
    red = tr.reduce_events(ev, chips)
    busy_ms = 1e3 * red["busy_s"] / red["steps"]
    assert 0.9 * busy_ms < sp["leaf"] < 1.1 * busy_ms
    exposed = 1e3 * sum(d["coll_exposed_s"] for d in red["devices"]) \
        / chips / red["steps"]
    per_axis = sum(v for k, v in sp.items() if k.startswith("exposed."))
    assert per_axis == pytest.approx(exposed, rel=0.01, abs=1e-9)
    if chips == 4:
        assert sp["exposed.tp"] > 0 and sp["exposed.dp"] > 0
    # no collective is left without its communicator
    assert not [o for d in ev["devices"].values() for o in d["ops"]
                if tr.COLLECTIVE.match(tr.instruction(o[0])[1])
                and ts.bucket(o) == "other"]
    # every kernel is named, and the name agrees with its shapes
    calls = collections.Counter(
        (ts.kernel_name(o[0]), tr.pallas_kernel(o[0]))
        for d in ev["devices"].values() for o in d["ops"]
        if "tpu_custom_call" in o[0])
    assert calls and all(a == b and a in ts.KERNELS for a, b in calls)
    # the train spans sit beside the benchmark's
    spans = {e[0] for e in ev["host"]}
    assert {"zmpi.train.grad", "zmpi.train.apply"} <= spans


@pytest.mark.parametrize("name,chips", SCOPED,
                         ids=["gpt2m-train-s1024", "gpt2l-train-dp2tp2"])
def test_fourth_field_leaves_reduction_unchanged(name, chips):
    ev = _load(name)
    bare = json.loads(json.dumps(ev))
    for d in bare["devices"].values():
        for o in d["ops"] + d["async"]:
            del o[3]
    bare["host"] = [e for e in bare["host"] if e[0].startswith("pb.")]
    a, b = tr.reduce_events(ev, chips), tr.reduce_events(bare, chips)
    for r in (a, b):
        for d in r["devices"]:
            d.pop("ops")
    assert a == b


def test_scopeless_recording_reads_nothing():
    """The recording of a program that names nothing: its ops carry no
    fourth field, so the scoped metrics are left out."""
    ev = _load("gpt2m-train-s1024.trace.json.gz")
    assert ts.split(ev, 1) is None and ts.readings(ev, 1) is None
