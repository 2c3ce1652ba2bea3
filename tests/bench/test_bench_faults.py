"""Drive a whole run, with no look for a chip, over a timed path broken
underneath, and see ``correct`` come out false: once for each fault a
served cell can have on one chip (a token altered where it is produced; a
decode step that returns its KV cache unchanged)."""
import json

import numpy as np
import pytest

import tiny
from bench import run

ARGS = ["--workload", "tiny-qwen2.chat", "--seed", "31", "--seconds", "3",
        "--trace", "0"]


def alter_tokens(eng):
    """Every seventh decode step serves the next token id on each row."""
    sample, n = eng._sample, [0]

    def bad(logits, rids, steps):
        out = np.array(sample(logits, rids, steps))
        n[0] += 1
        if n[0] % 7 == 3:
            out = (out + 1) % logits.shape[-1]
        return out
    eng._sample = bad


def keep_cache(eng):
    """Decode returns the cache it was given: no token's KV is written."""
    decode = eng._decode

    def bad(pt, cache, *rest):
        keep = {k: v.copy() for k, v in cache.items()}
        logits, _ = decode(pt, cache, *rest)
        return logits, keep
    eng._decode = bad


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("fault", [alter_tokens, keep_cache])
def test_fault_comes_out_not_correct(tree, fault, monkeypatch, capsys):
    setup = run.setup

    def broken(*a, **k):
        cfg, eng = setup(*a, **k)
        fault(eng)
        return cfg, eng
    monkeypatch.setattr(run, "setup", broken)
    run.main(ARGS, require_tpu=False, root=tree)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    gap = line["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]
