"""Traffic generators: every run of a mix offers the same schedule and
sizes; the seed draws the tokens, and the same seed the same tokens."""
import numpy as np
import pytest

from bench.kinds import finetune, serve
from bench.harness import load_cell

BIG = 2 ** 31 + 977
SERVE = ["stablelm-serve-prompt", "mamba2-serve-chat"]


def serve_mix(cell):
    return load_cell(cell).traffic


@pytest.mark.parametrize("cell", SERVE)
def test_schedule_is_fixed_and_holds_the_whole_mix(cell):
    """Every workflow of the mix is due inside the window, with the whole
    spread of prompt lengths, in one order that no seed changes."""
    t = serve_mix(cell)
    a = serve.schedule(t, 50)
    assert a == serve.schedule(t, 50)
    assert len(a) == round(t["rate_per_s"] * 50)
    lens = sorted(w["prompt_len"] for w in a)
    assert t["prompt_min"] <= lens[0] < lens[-1] <= t["prompt_max"]
    assert lens != [w["prompt_len"] for w in a]      # not sorted
    assert all(0 <= w["due"] < 50 for w in a)


def test_schedule_rate_and_bursts():
    t = {"rate_per_s": 2.0, "arrival_cv": 2.0, "prompt_min": 32,
         "prompt_max": 128}
    s = serve.schedule(t, 50)
    assert 90 <= len(s) <= 100
    gaps = np.diff([w["due"] for w in s])
    assert 1.5 < gaps.std() / gaps.mean() < 2.5


@pytest.mark.parametrize("cell", SERVE)
def test_prompts_follow_the_seed(cell):
    t = serve_mix(cell)
    w = serve.schedule(t, 50)[0]
    make = lambda seed: serve.make_prompts(
        50000, t["requests_per_workflow"], w["prompt_len"], seed, w["index"])
    assert (make(BIG) == make(BIG)).all()
    assert not (make(BIG) == make(BIG + 1)).all()


def test_prompts_and_batches_deterministic():
    a = serve.make_prompts(1000, 4, 9, BIG, 3)
    assert (a == serve.make_prompts(1000, 4, 9, BIG, 3)).all()
    assert not (a == serve.make_prompts(1000, 4, 9, BIG + 1, 3)).all()
    b = finetune.make_batches(1000, 2, 16, 3, BIG, 0)
    c = finetune.make_batches(1000, 2, 16, 3, BIG, 0)
    d = finetune.make_batches(1000, 2, 16, 3, BIG, 1)
    assert all((x["tokens"] == y["tokens"]).all()
               for x, y in zip(b["train"], c["train"]))
    assert not (b["train"][0]["tokens"] == d["train"][0]["tokens"]).all()
    rows = np.concatenate([x["tokens"] for x in b["train"]]
                          + [b["eval"]["tokens"]])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert (b["train"][0]["targets"][:, :-1]
            == b["train"][0]["tokens"][:, 1:]).all()


def test_ttft_counts_queue_wait():
    from types import SimpleNamespace
    from bench import records
    out = {"t_first": 10.5, "t_end": 11.0, "decode_s": 0.5}
    run = SimpleNamespace(records={"served": [
        {"ok": True, "out": out, "due_abs": 4.0, "requests": 2,
         "gen_len": 3}]})
    assert records.ttft_ms(run) == [6500.0, 6500.0]
    assert records.tpot_ms(run) == [250.0, 250.0]
