"""What a traced run hands to the per-layer metric readers.

A reader is ``bench/layer_metrics/<metric>.py`` with ``read(run)``, which
returns a number, or None where the run holds nothing for it to read (the
harness then leaves the metric out).  ``run`` is a :class:`RunView`:

  * ``spans``: the engine's tracer spans that started inside the window;
  * ``counters``: the scheduler's counters, as differences over the window;
  * ``trace``: the device trace of the window's first seconds, reduced
    (``lib/xtrace.py``), with ``trace_lo`` / ``trace_hi`` its own extent
    (first and last event, host or device) and ``steps`` the benchmark's
    ``bench.step`` spans inside it;
  * ``served``: the host's record of every request and the window;
  * the scheduler settings, the layers' matmul weights and the chip's
    peaks.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from . import flops, hlo, registry, xtrace
from .peaks import peak

HOST_SPANS = ("bench.step", "bench.offer", "bench.idle", "paged_step")
TOP = 10


@dataclass
class RunView:
    cell: Any
    scfg: Any
    served: Any
    spans: List[Any]
    counters: Dict[str, int]
    trace: Optional[xtrace.Trace]
    plane: Optional[str]
    trace_lo: float
    trace_hi: float
    steps: List[tuple]
    weights: int
    peaks: Dict[str, float]

    @classmethod
    def make(cls, cell, scfg, served, tracer, trace_dir,
             kind: str) -> "RunView":
        import jax
        t0, t1 = served.t0, served.t1
        spans = [s for s in tracer.events
                 if s.dur is not None and t0 <= s.ts < t1]
        counters = {k: served.counters1.get(k, 0) - v
                    for k, v in served.counters0.items()}
        trace = xtrace.Trace.load(trace_dir, HOST_SPANS)
        plane = sorted(trace.ops)[0] if trace.ops else None
        steps = trace.spans.get("bench.step", [])
        shapes = jax.eval_shape(
            lambda: cell.ref.make_weights(cell.config["model"], 0))
        return cls(cell, scfg, served, spans, counters, trace, plane,
                   trace.lo, trace.hi, steps, flops.matmul_weights(shapes),
                   peak(kind))

    # -- shared readings ------------------------------------------------------
    def window_s(self) -> float:
        return (self.trace_hi - self.trace_lo) / 1e9

    def busy_s(self) -> float:
        if self.plane is None:
            return 0.0
        return self.trace.busy(self.plane, self.trace_lo,
                               self.trace_hi) / 1e9

    def ops_in_window(self) -> List[xtrace.Op]:
        """Device ops inside the traced window, without the loops that
        contain other ops."""
        if self.plane is None:
            return []
        out = []
        for o in self.trace.ops[self.plane]:
            if o.start >= self.trace_lo and o.end <= self.trace_hi:
                p = hlo.parse(o.name)
                if p is None or p.kind not in hlo.CONTAINERS:
                    out.append(o)
        return out

    def breakdown(self) -> Dict[str, list]:
        """The device ops that took most time, and the longest idle gaps
        by the host span that covers them, in the traced window."""
        if self.plane is None:
            return {"device_ops": [], "idle_gaps": []}
        per: Dict[str, float] = {}
        for o in self.ops_in_window():
            label = op_label(o)
            per[label] = per.get(label, 0.0) + o.dur
        top = sorted(per.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = xtrace.idle_gaps(self.trace.ops[self.plane], self.trace_lo,
                                self.trace_hi)[:TOP]
        named = []
        for a, b in gaps:
            host = xtrace.covering(self.trace.spans, (a + b) / 2)
            named.append([host or "no host span", (b - a) / 1e9])
        return {"device_ops": [[n, s / 1e9] for n, s in top],
                "idle_gaps": named}

    def per_layer(self, entries: List[dict]):
        """-> (metrics, breakdown, busy_s, window_s)."""
        out: Dict[str, Dict[str, Any]] = {}
        for m in entries:
            value = registry.layer_metric(m["name"],
                                          self.cell.base).read(self)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out, self.breakdown(), self.busy_s(), self.window_s()


def op_label(op: xtrace.Op) -> str:
    """An op's name in the trace, kind and result shape."""
    p = hlo.parse(op.name)
    return p.label() if p is not None else op.name[:120]


# -- readers shared by several metric files -------------------------------------

def step_mfu(run: RunView) -> Optional[float]:
    """Model FLOPs of the tokens the window's steps processed over the
    summed step wall time times the int8 peak, in %."""
    m = run.cell.ref.dims(run.cell.config["model"])
    t0, t1 = run.served.t0, run.served.t1
    tokens = context = rows = 0
    for s in run.spans:
        if s.kind == "prefill_chunk":
            c, ctx = int(s.args["tokens"]), int(s.args["ctx"])
            tokens += c
            context += flops.chunk_context(ctx, c)
            rows += 1
    for r in run.served.records:
        for k, t in enumerate(r.tokens):
            if k and t0 <= t < t1:          # a decode step fed token k-1
                tokens += 1
                context += r.prompt_len + k
                rows += 1
    wall = xtrace.union_length(
        (s.ts, s.ts + s.dur) for s in run.spans
        if s.kind in ("schedule", "decode_step", "prefill_chunk"))
    if not tokens or wall <= 0:
        return None
    f = flops.model_flops(run.weights, m["layers"], m["h"], m["hd"], m["d"],
                          m["v"], tokens, context, rows)
    return 100.0 * f / (wall * run.peaks["int8_ops"])


def kernel_roofline(run: RunView, kernel: str) -> Optional[float]:
    """Sum over the kernel's launches in the traced window of the least
    time the chip could take (operations over the int8 peak or bytes over
    HBM bandwidth, whichever is longer) over the launches' device time, in
    %.  Operations and bytes come from each launch's own shapes, as the
    trace names them."""
    cost = hlo.COSTS[kernel]
    least = spent = 0.0
    for o in run.ops_in_window():
        p = hlo.parse(o.name)
        if p is None or p.base != kernel:
            continue
        ops, nbytes = cost(p)
        least += max(ops / run.peaks["int8_ops"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
        spent += o.dur / 1e9
    if not spent:
        return None
    return 100.0 * least / spent
