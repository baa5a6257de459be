"""moe.a2a_exposed_ms: the expert exchange's exposed time, per step, mean
over the chips, in ms: the time in which an all-to-all runs on a chip
(from an asynchronous start to its done, where the compiler splits one)
and no other operation does."""

import re

import trace_reduce

_A2A = re.compile(r"^all-to-all(-start|-done)?$")
_OPERAND = re.compile(r"all-to-all-done\(\S* ?%([^),\s]+)")


def intervals(ops) -> tuple[list, list]:
    """(all-to-all intervals, other operations' intervals) of one chip's
    leaf operations ``[text, start_ns, duration_ns]``: a done is joined to
    its start, so the flight between them counts."""
    a2a, other, starts = [], [], {}
    for text, t, d in ops:
        name, op = trace_reduce.instruction(text)
        if not _A2A.match(op):
            other.append((t, t + d))
        elif op == "all-to-all-start":
            starts[name] = t
        elif op == "all-to-all-done":
            m = _OPERAND.search(text)
            a2a.append((starts.pop(m.group(1), t) if m else t, t + d))
        else:
            a2a.append((t, t + d))
    a2a += [(t, t) for t in starts.values()]
    return a2a, other


def read(ctx):
    red = ctx["trace"]
    if red["steps"] == 0:
        return None
    total, seen = 0.0, False
    for dev in red["devices"]:
        a2a, other = intervals(dev["ops"])
        seen = seen or bool(a2a)
        total += trace_reduce._minus(trace_reduce._union(a2a),
                                     trace_reduce._union(other))
    if not seen:
        return None
    return total / 1e6 / len(red["devices"]) / red["steps"]
