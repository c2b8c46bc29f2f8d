"""The tracer's clock meets the profiler's: a tiny paged engine served
under ``jax.profiler`` on the CPU, with ``Tracer(jax_profiler=True)``,
and every ``device_step`` span mapped onto its ``paged_step`` annotation
within 100 us."""
import jax
import numpy as np

from bench.lib import align, view, xtrace
from repro.models import ModelConfig, init_params
from repro.obs import Tracer
from repro.serving.engine import PagedServeEngine, Request
from repro.serving.scheduler import SchedulerConfig

CFG = ModelConfig(name="align", vocab_size=128, d_model=64, n_layers=1,
                  n_heads=4, n_kv_heads=2, d_ff=128, attn_chunk=16)
SCFG = SchedulerConfig(block_size=16, num_blocks=24, max_batch=4,
                       max_blocks_per_req=8, prefill_chunk=16,
                       token_budget=64)


def _serve(eng, uid0):
    for i in range(3):
        eng.add_request(Request(
            uid=uid0 + i, prompt=(np.arange(20 + 8 * i, dtype=np.int32)
                                  * (3 + i)) % 128, max_new_tokens=6))
    eng.run()


def test_device_steps_map_onto_their_annotations(tmp_path):
    params = init_params(CFG, jax.random.PRNGKey(0))
    tr = Tracer(capacity=1 << 14, jax_profiler=True)
    eng = PagedServeEngine(params, CFG, SCFG, tracer=tr)
    _serve(eng, 0)                       # compile outside the trace
    tr.clear()
    opts = jax.profiler.ProfileOptions()  # as the harness traces: host
    opts.python_tracer_level = 0          # annotations, no Python calls
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(eng, 10)
    finally:
        jax.profiler.stop_trace()
    trace = xtrace.Trace.load(str(tmp_path), view.HOST_SPANS)
    ann = trace.spans["paged_step"]
    spans = [e for e in tr.events if e.dur is not None]
    steps = [e for e in spans if e.kind == "device_step"]
    assert len(ann) == len(steps) > 5
    fitted = align.fit(spans, ann)
    assert fitted is not None
    offset, worst, paired = fitted
    assert worst < align.TOL_NS
    assert paired == sorted(steps, key=lambda s: s.ts)
    for s, (a, b) in zip(paired, ann):
        lo, hi = align.on_trace(s, offset)
        assert abs(lo - a) < align.TOL_NS and abs(hi - b) < align.TOL_NS
