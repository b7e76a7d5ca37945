"""Where the device's idle time went, by the program's own host spans.

    python3 bench/tools/idle_by_span.py bench/out/<cell>/trace [--device 0]

Reads one profiler trace (a ``--trace 1`` run writes it under
``bench/out/<cell>/trace``). The program's spans (``core.tracing.span``:
``campaign.*``, ``runner.*``, ``service.*``) are host events on the
clock of the device's ``XLA Ops``. Each instant of the traced slice in
which the device ran nothing goes to the innermost program span open
then: among the spans covering it, on any thread, the one that started
last; time no span covers is ``(no span)``. Beside it, each span's self
time: its time on its own thread less that of the spans inside it.
Prints one JSON object, seconds throughout, spans in order of idle time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import trace_reduce  # noqa: E402
from scopes import innermost  # noqa: E402

PROGRAM_SPANS = ("campaign.", "runner.", "service.")
NO_SPAN = "(no span)"

Span = Tuple[int, int, str]


def program_spans(summary) -> Dict[int, List[Span]]:
    """Program spans inside the slice, by host thread (line index)."""
    out: Dict[int, List[Span]] = {}
    for p in summary.pd.planes:
        if p.name != trace_reduce.HOST_PLANE:
            continue
        for i, line in enumerate(p.lines):
            spans = [(int(e.start_ns), int(e.end_ns), e.name)
                     for e in line.events
                     if e.name.startswith(PROGRAM_SPANS)
                     and e.end_ns > summary.t0 and e.start_ns < summary.t1]
            if spans:
                out.setdefault(i, []).extend(spans)
    return out


def attribute(summary, device: int = 0) -> Dict:
    """Idle time of one device by innermost program span, and each
    span's self time, inside the traced slice."""
    by_thread = program_spans(summary)
    spans = [s for ss in by_thread.values() for s in ss]
    gaps = summary.gaps(device)
    idle = innermost(spans, summary.t0, summary.t1, mask=gaps,
                     uncovered=NO_SPAN)
    self_ns: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for ss in by_thread.values():
        for name, ns in innermost(ss, summary.t0, summary.t1).items():
            self_ns[name] = self_ns.get(name, 0.0) + ns
        for _, _, name in ss:
            count[name] = count.get(name, 0) + 1
    names = sorted(set(idle) | set(self_ns),
                   key=lambda n: -idle.get(n, 0.0))
    return {
        "device": device,
        "window_s": summary.window_s,
        "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "spans": {n: {"idle_s": idle.get(n, 0.0) * 1e-9,
                      "self_s": self_ns.get(n, 0.0) * 1e-9,
                      "count": count.get(n, 0)} for n in names},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="trace directory or .xplane.pb")
    ap.add_argument("--device", type=int, default=0)
    args = ap.parse_args(argv)
    summary = trace_reduce.Summary(trace_reduce.load(args.trace))
    print(json.dumps(attribute(summary, args.device), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
