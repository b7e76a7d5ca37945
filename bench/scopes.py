"""Device time by the program's named scopes, read from a profiler trace.

The program names each compiled layer with ``jax.named_scope``
(``SCOPES``) and its fused Pallas kernel with ``name="imc_fused"``,
which enters the name stack the same way. XLA keeps the name stack of
every HLO instruction in its ``op_name`` metadata, and the TPU profiler
keeps that as the ``tf_op`` stat of the op's event metadata, for
example ``jit(lane)/vmap(hamming_select)/gather`` or
``jit(lane)/while/body/ga_step/cost_model/add``: a transformation wraps
the scope's name (``vmap(hamming_select)``). A fusion carries its root
op's ``tf_op``, so an op fused into another scope's root counts under
that root's scope.

``jax.profiler.ProfileData`` does not expose event metadata, so this
module reads the ``.xplane.pb`` itself, with a schema of the few XSpace
fields it needs (field numbers of ``tsl/profiler/protobuf/xplane.proto``)
built with ``google.protobuf``: no TensorFlow in the benchmark's process.

Each device's ``XLA Ops`` events nest (a while loop holds its body's
ops). Every instant of the traced slice in which an op ran goes to the
innermost op running then, the one that started last: leaf time, where
a loop's own time outside its children stays with the loop. The op's
time goes to the chain of scope names in its ``tf_op``, outer to inner,
or to ``(unscoped)``. XLA leaves some ops without a ``tf_op``: the while
loops it rebuilds and the copies it inserts. Such an op takes the
scopes of what it runs or runs in (``_inherit``), so a loop's own time
goes to the scope of its body. Summed over chains this is the
device-busy time of ``trace_reduce.Summary``.

    python3 bench/scopes.py <trace dir or .xplane.pb>   # time by scope
"""
from __future__ import annotations

import functools
import heapq
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from trace_reduce import DEVICE_PLANE, OPS_LINE, find_xplane

SCOPES = ("cost_model", "accuracy_model", "noise_fields", "ga_step",
          "hamming_select", "nsga_rank", "imc_fused")
UNSCOPED = "(unscoped)"
TF_OP = "tf_op"
WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")

Chain = Tuple[str, ...]

# (message, [(field, number, type, repeated, message type)]); types are
# FieldDescriptorProto codes: 3 int64, 4 uint64, 1 double, 9 string,
# 11 message, 12 bytes
_SCHEMA = (
    ("XSpace", [("planes", 1, 11, True, "XPlane")]),
    ("XPlane", [("id", 1, 3, False, None), ("name", 2, 9, False, None),
                ("lines", 3, 11, True, "XLine"),
                ("event_metadata", 4, 11, True, "EventMetadataEntry"),
                ("stat_metadata", 5, 11, True, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, 3, False, None),
                            ("value", 2, 11, False, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, 3, False, None),
                           ("value", 2, 11, False, "XStatMetadata")]),
    ("XLine", [("id", 1, 3, False, None), ("name", 2, 9, False, None),
               ("timestamp_ns", 3, 3, False, None),
               ("events", 4, 11, True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, 3, False, None),
                ("offset_ps", 2, 3, False, None),
                ("duration_ps", 3, 3, False, None),
                ("stats", 4, 11, True, "XStat")]),
    ("XStat", [("metadata_id", 1, 3, False, None),
               ("double_value", 2, 1, False, None),
               ("uint64_value", 3, 4, False, None),
               ("int64_value", 4, 3, False, None),
               ("str_value", 5, 9, False, None),
               ("bytes_value", 6, 12, False, None),
               ("ref_value", 7, 4, False, None)]),
    ("XEventMetadata", [("id", 1, 3, False, None),
                        ("name", 2, 9, False, None),
                        ("stats", 5, 11, True, "XStat")]),
    ("XStatMetadata", [("id", 1, 3, False, None),
                       ("name", 2, 9, False, None)]),
)
_PACKAGE = "bench_xplane"


@functools.lru_cache(maxsize=None)
def messages() -> Dict[str, type]:
    """The schema's message classes by name (``XSpace``, ...)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=_PACKAGE, syntax="proto3")
    for name, fields in _SCHEMA:
        msg = fdp.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            f = msg.field.add(name=fname, number=number, type=ftype,
                              label=3 if repeated else 1)
            if type_name:
                f.type_name = f".{_PACKAGE}.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.{name}"))
        for name, _ in _SCHEMA}


def load_xspace(path: str):
    """The XSpace of a ``.xplane.pb``, or of the newest one under a
    trace directory."""
    if os.path.isdir(path):
        path = find_xplane(path)
    space = messages()["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def scope_chain(tf_op: str) -> Chain:
    """The scope names in a name stack, outer to inner, each unwrapped
    from the transformations around it (``vmap(hamming_select)``)."""
    chain = []
    for part in tf_op.rstrip(":").split("/"):
        m = WRAPPED.match(part)
        while m:
            part = m[1]
            m = WRAPPED.match(part)
        if part in SCOPES:
            chain.append(part)
    return tuple(chain)


def _stat_text(stat, names: Dict[int, str]) -> str:
    if stat.str_value:
        return stat.str_value
    return names.get(stat.ref_value, "") if stat.ref_value else ""


def _common(a: Chain, b: Chain) -> Chain:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


def _inherit(ops: List[list]) -> None:
    """Give each op that carries no name stack (``[s, e, None]``: a loop
    or copy that XLA built) the scopes it runs in: the common prefix of
    the chains of the named ops inside it, else its enclosing op's."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    inner: Dict[int, Chain] = {}
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]][1] <= ops[i][0]:
            stack.pop()
        chain = ops[i][2]
        if chain is not None:
            for a in stack:
                if ops[a][2] is None:
                    inner[a] = _common(inner[a], chain) if a in inner \
                        else chain
        stack.append(i)
    for i, c in inner.items():
        ops[i][2] = c
    stack = []
    for i in order:
        while stack and ops[stack[-1]][1] <= ops[i][0]:
            stack.pop()
        if ops[i][2] is None:
            ops[i][2] = ops[stack[-1]][2] if stack else ()
        stack.append(i)


def device_ops(space) -> List[List[Tuple[int, int, Chain]]]:
    """Per TPU device (in device order): (start ns, end ns, scope chain)
    of every ``XLA Ops`` event, on the clock and in the whole
    nanoseconds ``ProfileData`` reports. An op whose metadata holds no
    ``tf_op`` takes the scopes it runs in (``_inherit``)."""
    planes = sorted((p for p in space.planes if DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(DEVICE_PLANE.match(p.name)[1]))
    out = []
    for p in planes:
        stat_names = {e.key: e.value.name for e in p.stat_metadata}
        tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
        chains: Dict[int, Chain] = {}
        for entry in p.event_metadata:
            tf_op = next((_stat_text(s, stat_names)
                          for s in entry.value.stats
                          if s.metadata_id in tf_op_ids), "")
            if tf_op:
                chains[entry.key] = scope_chain(tf_op)
        ops = []
        for line in p.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                # whole ns, as ProfileData rounds them
                start = line.timestamp_ns + e.offset_ps // 1000
                ops.append([start, start + e.duration_ps // 1000,
                            chains.get(e.metadata_id)])
        _inherit(ops)
        out.append([tuple(op) for op in ops])
    return out


def innermost(items: Sequence[Tuple[float, float, object]],
              t0: float, t1: float,
              mask: Optional[Sequence[Tuple[float, float]]] = None,
              uncovered: Optional[object] = None) -> Dict[object, float]:
    """Time (in the items' unit) of [t0, t1] by the label of the
    innermost item covering it: among the items that cover an instant,
    the one that started last (the shorter one on a tie). With ``mask``
    (sorted, disjoint intervals) only masked time counts; time that no
    item covers goes to ``uncovered`` when it is given."""
    points = sorted((t, i) for i, (s, e, _) in enumerate(items) if e > s
                    for t in (s, e))
    out: Dict[object, float] = {}
    heap: List[Tuple[float, float, int]] = []   # (-start, end, index)
    m = 0

    def credit(a: float, b: float) -> None:
        nonlocal m
        a, b = max(a, t0), min(b, t1)
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        label = items[heap[0][2]][2] if heap else uncovered
        if b <= a or label is None:
            return
        if mask is None:
            out[label] = out.get(label, 0.0) + (b - a)
            return
        while m < len(mask) and mask[m][1] <= a:
            m += 1
        k = m
        while k < len(mask) and mask[k][0] < b:
            ov = min(b, mask[k][1]) - max(a, mask[k][0])
            if ov > 0:
                out[label] = out.get(label, 0.0) + ov
            k += 1

    prev = t0
    for t, i in points:
        if t > prev:
            credit(prev, t)
            prev = t
        s, e, _ = items[i]
        if t == s:
            heapq.heappush(heap, (-s, e, i))
    if t1 > prev:
        credit(prev, t1)
    return out


def leaf_time_by_chain(space, t0: float, t1: float
                       ) -> List[Dict[Chain, float]]:
    """Per device: seconds of [t0, t1] (ns) by the scope chain of the
    innermost op running; ``()`` is unscoped time."""
    return [{c: ns * 1e-9 for c, ns in innermost(ops, t0, t1).items()}
            for ops in device_ops(space)]


def leaf_time_by_scope(space, t0: float, t1: float
                       ) -> List[Dict[str, float]]:
    """Per device: seconds by the innermost scope, ``UNSCOPED`` for ops
    under none of ``SCOPES``."""
    out = []
    for by_chain in leaf_time_by_chain(space, t0, t1):
        d: Dict[str, float] = {}
        for chain, s in by_chain.items():
            k = chain[-1] if chain else UNSCOPED
            d[k] = d.get(k, 0.0) + s
        out.append(d)
    return out


def time_under(by_chain: Sequence[Dict[Chain, float]], scope: str) -> float:
    """Seconds, summed over devices, of ops anywhere under ``scope``
    (its inner scopes included)."""
    return sum(s for d in by_chain for c, s in d.items() if scope in c)


# ---------------------------------------------------------------------------
# what the metric readers share
# ---------------------------------------------------------------------------

def trace_dir(run) -> str:
    """Where ``harness.run_cell`` has the profiler write a run's trace."""
    return os.path.join(run.cell.bench_dir, "out", run.cell.name, "trace")


@functools.lru_cache(maxsize=4)
def _chains_cached(path: str, mtime: float, t0: float, t1: float):
    return leaf_time_by_chain(load_xspace(path), t0, t1)


def chains_of(run) -> Optional[List[Dict[Chain, float]]]:
    """Leaf time by scope chain per device inside the run's traced
    slice, or None when the run was not traced or left no trace."""
    if run.trace is None:
        return None
    try:
        path = find_xplane(trace_dir(run))
    except FileNotFoundError:
        return None
    return _chains_cached(path, os.path.getmtime(path),
                          float(run.trace.t0), float(run.trace.t1))


def busy_share(run, scope: str) -> Optional[float]:
    """Percent of device-busy time spent under ``scope``; None where the
    trace holds no op under it (a program without the scope)."""
    chains = chains_of(run)
    if chains is None:
        return None
    under = time_under(chains, scope)
    busy = sum(run.trace.busy_s)
    if under <= 0 or busy <= 0:
        return None
    return 100.0 * under / busy


if __name__ == "__main__":
    from trace_reduce import slice_bounds, load
    xs = load_xspace(sys.argv[1])
    a, b = slice_bounds(load(sys.argv[1]))
    print(json.dumps([dict(sorted(d.items(), key=lambda kv: -kv[1]))
                      for d in leaf_time_by_scope(xs, a, b)], indent=1))
