"""The serve cell: a whole run at test size, past the look for a chip.

Sound, ``correct`` is true; with each token altered where the engine's
decode step produces it, false.
"""
from bench.tests.cells import run_tiny, tiny_cell

SERVE = "serve.qwen1.5-4b.chat-zipf"
SEED = 3 * 2**31 + 7


def test_sound_run_is_correct():
    result, checks = run_tiny(tiny_cell(SERVE), SEED)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0


def test_serve_token_altered_is_caught(monkeypatch):
    from repro.serving import engine as engine_lib

    init = engine_lib.ServingEngine.__init__

    def altered(self, cfg, *a, **kw):
        init(self, cfg, *a, **kw)
        decode = self._decode_fn

        def wrong(*args):
            nxt, pool = decode(*args)
            return (nxt + 1) % cfg.vocab_size, pool

        self._decode_fn = wrong

    monkeypatch.setattr(engine_lib.ServingEngine, "__init__", altered)
    result, checks = run_tiny(tiny_cell(SERVE), SEED)
    assert not result["correct"], checks
