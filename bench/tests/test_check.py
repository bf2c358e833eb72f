"""The comparison that decides ``correct``, driven through the harness at
a test size on the CPU: sound runs pass, a run with the fp8 control in the
program's place comes out not correct, and so does a run whose timed path
is broken.  The harness's
look for a chip is skipped (``device=None``); everything else is a run."""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

import cell
from reference import control_gaps, served_gaps
from tests_common import DATA

BENCH = json.loads((DATA / "BENCHMARK.json").read_text())
SEEDS = [3, 2 ** 33 + 1]


def _run(name, seed, **kw):
    return cell.run_cell(BENCH, name, seed, 2.0, False,
                         t_start=time.perf_counter(), device=None, data=DATA,
                         **kw)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["tiny-gqa.saturated",
                                  "tiny-mha-bias.saturated"])
def test_sound_run_is_correct_and_control_is_not(name, seed):
    res = _run(name, seed)
    assert res["correct"], res["checks"]
    assert res["checks"]["sampled_tokens"]["value"] >= cell.MIN_SAMPLE_TOKENS
    assert list(res)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res

    got = {}

    def program_then_control(spec, s, samples, **kw):
        got["program"] = float(np.max(served_gaps(spec, s, samples, **kw)))
        return control_gaps(spec, s, samples, **kw)

    ctrl = _run(name, seed, gaps=program_then_control)
    assert ctrl["correct"] is False
    assert ctrl["checks"]["max_logit_gap"]["value"] > \
        ctrl["checks"]["max_logit_gap"]["limit"] >= got["program"]


def _alter_tokens(eng):
    """A token altered where it is produced: every decode step's logits
    are shifted by one vocabulary row, so the step emits the token after
    its argmax."""
    decode = eng._decode

    def broken(*args, **kw):
        logits, cache = decode(*args, **kw)
        return jnp.roll(logits, 1, axis=-1), cache
    eng._decode = broken


def _alter_last_slot(eng):
    """A token altered in one slot only: the last slot's decode logits are
    shifted by one vocabulary row.  The sample holds a request from every
    slot that finished one, so this one is checked too."""
    decode = eng._decode
    last = eng.slots.n_slots - 1

    def broken(*args, **kw):
        logits, cache = decode(*args, **kw)
        return logits.at[last].set(jnp.roll(logits[last], 1, axis=-1)), cache
    eng._decode = broken


def _drop_chunk_writes(eng):
    """A step that returns its state unchanged: prompt chunks leave the KV
    pool as it was, so decode attends to stale keys and values."""
    run_chunk = eng._run_chunk

    def broken(toks, n_valid, pos_offset, bt):
        cache = eng.slots.cache
        logits, _ = run_chunk(toks, n_valid, pos_offset, bt)
        return logits, cache
    eng._run_chunk = broken


@pytest.mark.parametrize("fault", [_alter_tokens, _alter_last_slot,
                                   _drop_chunk_writes])
def test_broken_timed_path_is_not_correct(fault):
    res = _run("tiny-gqa.saturated", SEEDS[0], stage=fault)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]
