"""The program's own names in a trace, and the split of the step they give.

The program names its device work where it issues it: ``jax.named_scope``
writes ``zmpi.attn``, ``zmpi.mlp``, ``zmpi.loss`` and ``zmpi.optimizer``
into the HLO metadata of the model's ops, each communicator writes
``zmpi.coll.<axis>.<name>.<op>`` into that of the collectives it issues,
and ``make_train_step_optax`` puts host spans ``zmpi.train.grad`` and
``zmpi.train.apply`` around its two dispatches.  On a v5e the metadata is
not a stat of an op's event: it is the ``tf_op`` stat of the event's
metadata on the device plane, which ``jax.profiler.ProfileData`` does not
show, so :func:`op_names` reads it from the ``.xplane.pb`` itself.

``load_events`` gives what ``trace_reduce.load_events`` gives, each device
op and asynchronous flight with a fourth field (the ``zmpi.`` components of
its ``op_name``, outermost first) and the ``zmpi.`` host spans beside the
``pb.`` ones; ``trace_reduce.reduce_events`` reads it unchanged.
``split`` divides the leaf-op time of the traced steps by those names;
``idle_gaps`` labels the device's idle gaps with the innermost host span.

    python3 perfbench/trace_scopes.py <xplane.pb> <out.json.gz>

writes the kept events, as the scoped recordings under ``testdata/`` were
made (cell 2's then cut to its middle traced step).
"""

from __future__ import annotations

import gzip
import json
import re
import sys

import trace_reduce as tr

HOST_PREFIX = "zmpi."
SCOPE = re.compile(r"zmpi\.[^/()\s;:]*")
MODEL = {"zmpi.attn": "attn", "zmpi.mlp": "mlp", "zmpi.loss": "loss",
         "zmpi.optimizer": "opt"}
COMM = re.compile(r"^zmpi\.coll\.([^.]+)\.")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "layernorm_fwd",
           "layernorm_bwd")


# ------------------------------------------------------------ the xplane


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: a varint
    as an int, a length-delimited field as bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = bytes(buf[i:i + n]), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _map_entry(buf):
    kv = dict(_fields(buf))
    return kv.get(1, 0), kv.get(2, b"")


def op_names(path: str) -> dict:
    """``{device plane name: {op's HLO text: op_name}}``: the ``tf_op``
    stat of each event metadata of the ``/device:`` planes of an
    ``XSpace`` (XSpace.planes 1; XPlane name 2, event_metadata 4,
    stat_metadata 5; XEventMetadata name 2, stats 5; XStat metadata_id 1,
    str_value 5, ref_value 7)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = v.decode()
            elif pf == 4:
                events.append(_map_entry(v)[1])
            elif pf == 5:
                sid, meta = _map_entry(v)
                stat_names[sid] = dict(_fields(meta)).get(2, b"").decode()
        if not tr.DEVICE_PLANE.match(name):
            continue
        ops = out.setdefault(name, {})
        for meta in events:
            text, tf_op = "", ""
            for ef, v in _fields(meta):
                if ef == 2:
                    text = v.decode()
                elif ef == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    tf_op = (stat[5].decode() if 5 in stat
                             else stat_names.get(stat.get(7), ""))
            if tf_op:
                ops.setdefault(text, tf_op)
    return out


def scopes(op_name: str) -> list[str]:
    """The ``zmpi.`` components of an op_name, outermost first, with JAX's
    own name stack (``jit(..)``, ``transpose(jvp(..))``, ``while/body``)
    stripped.  An op XLA merged from several reads its first name."""
    return SCOPE.findall(op_name.split(";")[0])


def load_events(path: str) -> dict:
    from jax.profiler import ProfileData

    events = tr.load_events(path)
    names = op_names(path)
    for d, dev in events["devices"].items():
        of = names.get(f"/device:TPU:{d}", {})
        for o in dev["ops"] + dev["async"]:
            o.append(scopes(of.get(o[0], "")))
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events["host"] += [[ev.name, ev.start_ns, ev.duration_ns]
                                   for ev in line.events
                                   if ev.name.startswith(HOST_PREFIX)]
    events["host"].sort(key=lambda e: e[1])
    return events


# ------------------------------------------------------------- the split


def kernel_name(text: str) -> str | None:
    """The kernel a ``tpu_custom_call`` event's instruction is named for
    (``%flash_fwd.3``, or ``%jvp_layernorm_fwd_.1`` outside the layer
    loop), else None."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    name = tr.instruction(text)[0]
    hits = [k for k in KERNELS if k in name]
    return hits[0] if len(hits) == 1 else None


def bucket(op: list) -> str:
    """Where an op's time goes: a collective to ``coll.<axis>`` of the
    communicator scope it carries (``other`` without one), any other op
    to its innermost model scope, else ``other``."""
    names = op[3]
    if tr.COLLECTIVE.match(tr.instruction(op[0])[1]):
        axes = [m.group(1) for m in map(COMM.match, names) if m]
        return f"coll.{axes[-1]}" if axes else "other"
    model = [MODEL[s] for s in names if s in MODEL]
    return model[-1] if model else "other"


def _window(events):
    host = events["host"]
    steps = [e for e in host if e[0] == "pb.step"]
    if not steps:
        raise RuntimeError("no pb.step span in the trace")
    ends = [e for e in host if e[0] in ("pb.step", "pb.fetch")]
    return (min(e[1] for e in steps), max(e[1] + e[2] for e in ends),
            len(steps))


def split(events: dict, n_devices: int) -> dict | None:
    """Per traced step, mean over chips, in ms: the leaf-op time of each
    bucket that holds an op (``attn``, ``mlp``, ``loss``, ``opt``,
    ``other`` and the collectives' ``coll.<axis>``), their sum ``leaf``,
    and ``exposed.<axis>``: the time of that axis's collective ops and
    flights during which no other operation runs, as ``coll_exposed_s``
    of ``trace_reduce`` counts it for all collectives together.

    None when no device op carries a ``zmpi.`` scope: the program that
    made the trace does not name its work."""
    t0, t1, steps = _window(events)
    devs = sorted(events["devices"], key=int)[:n_devices]
    if not any(o[3:] and o[3] for d in devs
               for o in events["devices"][d]["ops"]):
        return None
    out, per = {"leaf": 0.0}, 1e-6 / len(devs) / steps
    for d in devs:
        dev = events["devices"][d]
        comp, coll = [], {}
        for o in dev["ops"]:
            op = tr.instruction(o[0])[1]
            if not (o[1] < t1 and o[1] + o[2] > t0) or op in tr.WRAPPER:
                continue
            b = bucket(o)
            out[b] = out.get(b, 0.0) + o[2] * per
            out["leaf"] += o[2] * per
            iv = (o[1], o[1] + o[2])
            if b.startswith("coll."):
                coll.setdefault(b[5:], []).append(iv)
            elif not tr.COLLECTIVE.match(op):
                comp.append(iv)
        for o in dev.get("async", ()):
            b = bucket(o)
            if o[1] < t1 and o[1] + o[2] > t0 and b.startswith("coll."):
                coll.setdefault(b[5:], []).append((o[1], o[1] + o[2]))
        comp = tr._union(comp)
        for axis, ivs in coll.items():
            key = f"exposed.{axis}"
            out[key] = out.get(key, 0.0) \
                + tr._minus(tr._union(ivs), comp) * per
    return out


# the per-layer metrics these names feed, and the part of the split each
# reads
METRICS = {"attn.ms": "attn", "mlp.ms": "mlp", "loss.ms": "loss",
           "opt.ms": "opt", "other.ms": "other",
           "coll.tp_exposed_ms": "exposed.tp",
           "coll.dp_exposed_ms": "exposed.dp"}


def readings(events: dict, n_devices: int) -> dict | None:
    """Each of :data:`METRICS` from a trace: None for a metric whose
    scope holds no op (a reader with nothing to read); the whole result
    None when the program names nothing, so that the metrics are left
    out, never read as 0."""
    parts = split(events, n_devices)
    if parts is None:
        return None
    return {m: parts.get(k) for m, k in METRICS.items()}


def idle_gaps(events: dict, n_devices: int, top: int = 10) -> list:
    """The longest idle gaps of the traced window, each as ``[label,
    seconds]``: the innermost host span over most of the gap (of those
    overlapping it most, the shortest), so a gap during the AdamW
    dispatch reads ``zmpi.train.apply`` rather than ``pb.dispatch``."""
    t0, t1, _ = _window(events)
    gaps = []
    for d in sorted(events["devices"], key=int)[:n_devices]:
        ops = [o for o in events["devices"][d]["ops"]
               if o[1] < t1 and o[1] + o[2] > t0]
        busy = tr._union((max(o[1], t0), min(o[1] + o[2], t1)) for o in ops)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps += [(e - s, s, e) for s, e in zip(edges[::2], edges[1::2])
                 if e > s]
    gaps.sort(reverse=True)
    return [[_innermost(events["host"], s, e), g / 1e9]
            for g, s, e in gaps[:top]]


def _innermost(host, s, e) -> str:
    best, label = (0, 0), "no host span"
    for name, hs, hd in host:
        if name == "pb.step":
            continue
        ov = min(e, hs + hd) - max(s, hs)
        if ov > 0 and (ov, -hd) > best:
            best, label = (ov, -hd), name
    return label


if __name__ == "__main__":
    ev = load_events(sys.argv[1])
    with gzip.open(sys.argv[2], "wt") as f:
        json.dump(ev, f)
