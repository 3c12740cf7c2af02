"""The harness end to end on the CPU, at the program's reduced glm4-9b
sizes in float32: a sound run is correct, the runs with the timed path
broken underneath are not, and the float8 control falls outside the
limit the sound runs keep inside."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import control, files  # noqa: E402
from chipbench import run as bench  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CELL = "tiny.decode"
SEED = 2**33 + 11


def _run(capsys, fault=None, seed=SEED, allow_cpu=True):
    rc = bench.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
                     "--trace", "0"], allow_cpu=allow_cpu, root=DATA,
                    benchmark=DATA / "bench.json", fault=fault)
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err


def test_sound_run_is_correct(capsys):
    rc, res, err = _run(capsys)
    assert rc == 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 8 == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"output_tok_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert list(res)[-1] == "check"
    assert res["check"]["served_gap"]["value"] <= res["check"]["served_gap"]["limit"]
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    # every shape was warmed up: nothing compiles inside the window
    assert "compiles inside the window: 0 backend, new engine variants none;" in err
    # the numbers compared are the last lines of standard error
    assert err.strip().splitlines()[-1].startswith("[chipbench] check failed_requests")


def test_without_a_chip_the_run_fails_and_prints_no_result(capsys):
    rc, res, err = _run(capsys, allow_cpu=False)
    assert rc == 1 and res is None
    assert "needs a TPU" in err


def _patch_decode(engine, wrap):
    orig = engine._paged_decode_fn

    def patched(bound):
        return wrap(orig(bound))

    engine._paged_decode_fn = patched


def alter_token(engine):
    """The decode step's token is replaced by its neighbour in the
    vocabulary where it is produced."""
    import jax.numpy as jnp

    vocab = engine.model.cfg.vocab_size

    def wrap(fn):
        def step(params, nxt, cache, table, pos, mask):
            tok, new_nxt, new_pos, cache = fn(params, nxt, cache, table, pos, mask)
            bad = (tok + 1) % vocab
            return bad, jnp.where(mask, bad, new_nxt), new_pos, cache
        return step

    _patch_decode(engine, wrap)


def keep_state(engine):
    """The decode step hands back the state it was given: next tokens,
    positions and page pool as they were before the step."""
    import jax
    import jax.numpy as jnp

    def wrap(fn):
        def step(params, nxt, cache, table, pos, mask):
            kept = jax.tree.map(jnp.copy, (nxt, pos, cache))
            tok, _, _, _ = fn(params, nxt, cache, table, pos, mask)
            return tok, kept[0], kept[1], kept[2]
        return step

    _patch_decode(engine, wrap)


@pytest.mark.parametrize("fault", [alter_token, keep_state],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(capsys, fault):
    rc, res, err = _run(capsys, fault=fault)
    assert rc == 0
    assert res["correct"] is False
    gap = res["check"]["served_gap"]
    assert gap["value"] > gap["limit"]


def test_control_falls_outside_the_limit():
    cell = files.load_cell(CELL, root=DATA, benchmark=DATA / "bench.json")
    limit = cell.serve["check"]["limits"]["served_gap"]
    for out in control.readings(cell, [3, 2**34 + 1], allow_cpu=True):
        assert out["served_gap"] <= limit < out["control_gap"]
        assert out["control_gap"] >= 3 * out["served_gap"]
