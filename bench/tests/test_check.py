"""The correctness check at a CPU test size: a sound run passes, the fp8
control fails, and a run whose timed path is broken underneath reads
``correct`` false, once per fault a serving cell can have.

Each case drives ``harness.run_cell`` past the entry's TPU check, on the
tiny configuration in ``data/`` (the glm4-9b layout at test widths, bf16)
with its own limit (``data/checks/tiny.decode.json``)."""
import json
import time

import numpy as np
import pytest

import harness
from conftest import REPO, HERE

DATA = f"{HERE}/data"
SEED = 2147483659


def _bench():
    with open(f"{REPO}/BENCHMARK.json") as f:
        b = json.load(f)
    b["workloads"] = [{"name": "tiny.decode", "config": "tiny", "traffic": "tiny",
                       "chips": 1}]
    return b


def _run(patch=None, control=False):
    return harness.run_cell("tiny.decode", SEED, 4.0, False, t_process=time.time(),
                            bench=_bench(), root=DATA, patch=patch, control=control)


def test_sound_run_passes_and_fp8_control_fails():
    out = _run(control=True)
    assert out["correct"], out["check"]
    assert out["check"]["tokens_compared"]["value"] > 20
    assert out["control"]["correct"] is False, out["control"]
    assert out["control"]["max_logit_gap"]["limit"] == \
        out["check"]["max_logit_gap"]["limit"]
    assert list(out)[-1] == "check"


def _state_unchanged(bridge, obs, monkeypatch):
    """The decode step returns the KV cache it was given."""
    inner = obs.decode_fn

    def decode(params, toks, pos, caches):
        logits, _new, hid = inner(params, toks, pos, caches)
        return logits, caches, hid
    obs.decode_fn = decode


def _half_batch(bridge, obs, monkeypatch):
    """The decode step leaves out the second half of the batch."""
    inner = obs.decode_fn

    def decode(params, toks, pos, caches):
        logits, new, hid = inner(params, toks, pos, caches)
        hid = np.array(hid)
        hid[hid.shape[0] // 2:] = 0
        return logits, new, hid
    obs.decode_fn = decode


def _token_altered(bridge, obs, monkeypatch):
    """The coded head's decoded logits of one row are shifted by one id,
    so the token produced from them is another."""
    from repro.serve_coded import packing
    orig = packing.PackedStage.execute

    def execute(self, X, **kw):
        out = orig(self, X, **kw)
        if "head" in out:
            z = np.array(out["head"])
            z[0] = np.roll(z[0], 1)
            out["head"] = z
        return out
    monkeypatch.setattr(packing.PackedStage, "execute", execute)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _token_altered],
                         ids=["state_unchanged", "half_batch", "token_altered"])
def test_broken_timed_path_reads_not_correct(fault, monkeypatch):
    out = _run(patch=lambda bridge, obs: fault(bridge, obs, monkeypatch))
    assert not out["correct"], out["check"]
