"""A configuration, traffic mix, traffic generator, limit and per-layer
metric added as new files are found by name, with no edit to any existing
file."""
import json
import textwrap

import numpy as np
import pytest

from bench.lib import harness, registry


def test_new_files_are_found(tmp_path):
    for d in ("configs", "traffic", "checks", "layer_metrics", "reference",
              "generators"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps({"reference": "plain", "model": {"hidden_size": 8}}))
    (tmp_path / "traffic" / "new-mix.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 2.0}))
    (tmp_path / "checks" / "new-model.new-mix.json").write_text(
        json.dumps({"widest_logit_gap": {"limit": 0.5}}))
    (tmp_path / "layer_metrics" / "new_metric.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    (tmp_path / "reference" / "plain.py").write_text("ANSWER = 7\n")
    (tmp_path / "generators" / "new_gen.py").write_text("KIND = 'x'\n")
    assert registry.config("new-model", tmp_path)["model"]["hidden_size"] == 8
    assert registry.traffic("new-mix", tmp_path)["rate_per_s"] == 2.0
    assert registry.limits("new-model.new-mix", tmp_path)[
        "widest_logit_gap"]["limit"] == 0.5
    assert registry.layer_metric("new_metric.x", tmp_path).read(None) == 42.0
    assert registry.reference("plain", tmp_path).ANSWER == 7
    assert registry.generator("new_gen", tmp_path).KIND == "x"


def test_missing_file_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        registry.traffic("nope", tmp_path)


def test_every_cell_finds_its_files():
    """Each cell of BENCHMARK.json has its configuration, mix, limits and
    reference; each per-layer metric has its reader."""
    bench = registry.benchmark(registry.BENCH.parent)
    for c in bench["workloads"]:
        cfg = registry.config(c["config"])
        mix = registry.traffic(c["traffic"])
        assert callable(registry.generator(mix["generator"]).make)
        registry.limits(c["name"])
        registry.reference(cfg["reference"])
    for m in bench["per_layer"]:
        assert callable(registry.layer_metric(m["name"]).read)


SESSIONS = textwrap.dedent("""
    import numpy as np
    from bench.lib.traffic import Spec

    class Sessions:
        \"\"\"Each of ``users`` sends ``turns`` requests, the next one when
        the last is answered; one more request is due at ``late_s``.\"\"\"
        def __init__(self, mix, seed, vocab):
            self.mix, self.n = mix, 0
            self.rng = np.random.default_rng(seed)
            self.turn = {}

        def _spec(self, due=0.0):
            s = Spec(self.n, self.rng.integers(0, 50, 4).astype(np.int32),
                     3, due)
            self.n += 1
            return s

        def first_wave(self):
            out = [self._spec() for _ in range(self.mix["users"])]
            self.turn.update({s.index: 1 for s in out})
            return out

        def after(self, index):
            t = self.turn.pop(index, None)      # None: the timed one
            if t in (None, self.mix["turns"]):
                return []
            s = self._spec()
            self.turn[s.index] = t + 1
            return [s]

        def arrivals(self):
            yield self._spec(self.mix["late_s"])

    make = Sessions
""")


class _Scheduler:
    def __init__(self):
        self.stats = {"prefill_chunks": 0}
        self.running = []

    @property
    def has_work(self):
        return bool(self.running)


class _Engine:
    """Emits one token a step for every request it holds."""

    def __init__(self):
        self.scheduler = _Scheduler()

    def add_request(self, req):
        req.generated = []
        self.scheduler.running.append(req)

    def step(self):
        for r in list(self.scheduler.running):
            r.generated.append(1)
            r.on_token(r, 1)
            if len(r.generated) == r.max_new_tokens:
                self.scheduler.running.remove(r)


def test_new_generator_is_driven(tmp_path):
    """A generator and a mix added as new files are found and drive the
    harness's serving loop: the first wave, follow-ups on each finish, and
    a timed arrival."""
    (tmp_path / "generators").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "generators" / "sessions.py").write_text(SESSIONS)
    (tmp_path / "traffic" / "chat.json").write_text(json.dumps(
        {"generator": "sessions", "users": 2, "turns": 3, "late_s": 0.01,
         "ramp_s": 0.0}))
    mix = registry.traffic("chat", tmp_path)
    traffic = registry.generator(mix["generator"], tmp_path).make(mix, 5, 50)
    served = harness.serve(_Engine(), traffic, mix, seconds=0.05)
    done = [r for r in served.records if len(r.tokens) == r.max_new]
    assert len(served.records) == 2 * 3 + 1 == len(done)
    assert sorted(np.unique([r.index for r in served.records])) == \
        list(range(7))
