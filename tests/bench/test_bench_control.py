"""``correct`` must come out false for the lower-precision control and for
faults planted under a run. Each test drives the tiny CPU cell through the
whole runner, the chip check skipped."""
from bench import harness


def test_control_is_not_correct(tiny_root, run_tiny):
    """The control in the program's place, judged by the run's own checks."""
    cell = harness.load_cell("tiny.cell", root=tiny_root)
    for seed in (5, 6, 7):
        res, _ = run_tiny(tiny_root, seed=seed)
        assert res.correct, res.checks
        checks, correct = cell.runner.control(cell, res, seed)
        assert not correct
        assert checks["mean_gap"]["value"] > checks["mean_gap"]["limit"]
        assert checks["failed"]["value"] == 0


def test_token_altered_where_produced_is_not_correct(tiny_root, run_tiny,
                                                      monkeypatch):
    from repro.serve.batched import BatchedEngine

    rounds = BatchedEngine._rounds

    def altered(self):
        chunk = rounds(self).copy()
        chunk[:, 0] = (chunk[:, 0] + 1) % self.cfg.vocab_size
        return chunk

    monkeypatch.setattr(BatchedEngine, "_rounds", altered)
    res, _ = run_tiny(tiny_root)
    assert not res.correct
    assert res.checks["mean_gap"]["value"] > res.checks["mean_gap"]["limit"]
    assert res.checks["failed"]["value"] == 0


def test_short_answer_is_a_failed_request(tiny_root, run_tiny, monkeypatch):
    from repro.serve.batched import BatchedEngine

    run = BatchedEngine.run

    def short(self, requests):
        out = run(self, requests)
        uid = min(out)
        out[uid] = out[uid][:-1]
        return out

    monkeypatch.setattr(BatchedEngine, "run", short)
    res, _ = run_tiny(tiny_root)
    assert not res.correct and res.failed > 0
