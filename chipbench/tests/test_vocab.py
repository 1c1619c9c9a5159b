"""The harness works on the published vocabulary (the configuration's
``vocab_size``), not on the rows of the program's table, which may be
padded: prompts and first tokens come from it, a served padding id is a
request served wrong, and the configuration's ``program_fields`` hold the
program to the published settings, a field it lacks included."""

import hashlib
import json

import jax.numpy as jnp
import numpy as np
import pytest

import run as harness
import smoke
import traffic as traffic_lib
from ref.common import widest_gap

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
QWEN3_CELLS = [w["name"] for w in BENCH["workloads"]
               if w["config"].startswith("qwen3")]
PADDED = QWEN3_CELLS[0]
PADDING = 3
#: sha256 (first 16 hex digits) of the warm batch and two cycles of
#: batches (prompt bytes, then asked-for tokens) that the parent tree's
#: ``Traffic`` drew over the program's 151936 rows, per cell and seed
PARENT_TRAFFIC = {
    ("qwen3-1.7b.chat-restart", 1): "394c93cd83a54a56",
    ("qwen3-1.7b.chat-restart", 2 ** 31 + 3): "17cb9b98592f831c",
    ("qwen3-1.7b.chat-restart", 2 ** 33): "49a78074234092a8",
    ("qwen3-1.7b.decode-long", 1): "16c4a64b57bf615a",
    ("qwen3-1.7b.decode-long", 2 ** 31 + 3): "302951be4a94b309",
    ("qwen3-1.7b.decode-long", 2 ** 33): "1f72cb7b47e163a9",
}


def _config(name):
    return json.loads((harness.BENCH / "configs" / f"{name}.json")
                      .read_text())


def _padded_files():
    """A qwen3 cell at the smoke size whose published vocabulary lies
    PADDING rows below the program's table."""
    files = smoke.smoke_files(PADDED)
    files["config"]["vocab_size"] -= PADDING
    return files


def _driver(files, devices=None):
    ctx = harness.Context(files, 2 ** 31 + 5, devices, smoke=True)
    return harness.load_module(files["driver"], "driver_vocab").Driver(ctx)


def _set_up(files):
    driver = _driver(files, harness.configure_jax().devices()[:1])
    driver.setup()
    return driver


def test_smoke_padding():
    """The smoke size keeps the file's padding."""
    from repro.configs import get_smoke_config
    rows = get_smoke_config("mamba2-2.7b").vocab
    assert smoke.smoke_config(_config("mamba2-2.7b"))["vocab_size"] \
        == rows - PADDING
    assert smoke.smoke_config(_config("mamba2-2.7b"))["padded_vocab_size"] \
        == rows
    rows = get_smoke_config("qwen3-1.7b").vocab
    assert smoke.smoke_config(_config("qwen3-1.7b"))["vocab_size"] == rows


def test_prompts_stay_in_the_published_vocabulary():
    driver = _set_up(_padded_files())
    rows = driver.cfg.vocab
    assert driver.vocab == driver.traffic.vocab == rows - PADDING
    top = 0
    for seed in range(50):
        t = traffic_lib.Traffic(driver.mix, 2 ** 31 + seed, driver.vocab)
        for b in [t.warm()] + [t.batch(i) for i in range(4)]:
            assert b.prompts.min() >= 0
            assert b.prompts.max() < driver.vocab
            top = max(top, int(b.prompts.max()))
    assert top == driver.vocab - 1


def test_widest_gap_of_a_token_outside_the_logits():
    logits = np.arange(10.0).reshape(2, 5)
    assert widest_gap(logits, np.array([4, 3])) == 1.0
    assert widest_gap(logits, np.array([4, 5])) == float("inf")
    assert widest_gap(logits, np.array([-1, 4])) == float("inf")


def test_served_padding_id_is_not_correct():
    """A hand-made sample whose served tokens hold a padding id: the check
    reads an infinite gap and the request counts as served wrong."""
    from repro.serving.engine import Request
    driver = _set_up(_padded_files())
    batch = driver.traffic.batch(0)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(batch.prompts, batch.new_tokens))]
    for r in reqs:
        r.generated = [1] * r.max_new_tokens
    reqs[3].generated[-1] = driver.vocab          # the first padding row
    rec = {"requests": reqs, "rows": len(reqs),
           "first_tokens": np.ones(len(reqs), np.int32)}
    assert driver.incomplete(rec) == 1
    checked = driver.check([rec])
    assert checked["widest_gap"] == float("inf")


def _logits_patched(monkeypatch, change):
    """The program's decode step with ``change(logits, index)`` applied to
    the logits it returns."""
    from repro.models import registry
    step = registry.decode_step

    def patched(params, cfg, token, index, caches):
        logits, caches = step(params, cfg, token, index, caches)
        return change(logits, index), caches
    monkeypatch.setattr(registry, "decode_step", patched)


def _run(files, seed=2 ** 31 + 17):
    return harness.run_cell(PADDED, seed, 0.5, False, files=files,
                            require_tpu=False, smoke=True, log=lambda m: None)


def test_padded_run_of_a_program_that_serves_the_published_vocabulary(
        monkeypatch):
    """With the padding rows never chosen, as a program that serves only
    the published vocabulary does, a padded run comes out correct."""
    files = _padded_files()
    v = files["config"]["vocab_size"]
    _logits_patched(monkeypatch,
                    lambda x, _: x.at[..., v:].set(jnp.finfo(x.dtype).min))
    result, _ = _run(files)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_padded_run_that_serves_a_padding_id(monkeypatch):
    """A program that serves a padding row gets a result, not an exception:
    not correct, every request served wrong, and a gap that strict JSON
    can carry."""
    files = _padded_files()
    v = files["config"]["vocab_size"]
    _logits_patched(monkeypatch,
                    lambda x, _: x.at[..., v].set(jnp.finfo(x.dtype).max))
    result, compared = _run(files)
    assert not result["correct"]
    assert result["failed"] == result["attempted"] > 0
    assert dict((n, x) for n, x, _ in compared)["widest_gap"] == float("inf")
    assert result["checks"]["widest_gap"]["value"] == "inf"
    json.loads(json.dumps(result), parse_constant=pytest.fail)


def test_first_token_is_the_one_the_engine_feeds_forward(monkeypatch):
    """Where only the last prompt position puts its top logit on a padding
    row, the engine feeds that row forward: it is the served first token,
    the request is served wrong, and the check reads an infinite gap."""
    driver = _set_up(_padded_files())
    batch = driver.traffic.batch(0)
    last, v = batch.prompts.shape[1] - 1, driver.vocab
    _logits_patched(monkeypatch, lambda x, index: jnp.where(
        index == last, x.at[..., v].set(jnp.finfo(x.dtype).max), x))
    rec = driver._serve(batch)
    assert (rec["first_tokens"] == v).all()
    assert driver.incomplete(rec) == rec["rows"]
    assert driver.check([rec])["widest_gap"] == float("inf")


@pytest.mark.parametrize("fields, named", [
    ({"no_such_field": 1}, "'no_such_field': (None, 1)"),
    ({"norm_eps": 1e-05}, "'norm_eps': (1e-06, 1e-05)"),
])
@pytest.mark.parametrize("size", ["full", "smoke"])
def test_program_fields_name_each_departure(fields, named, size):
    config = _config("qwen3-1.7b")
    if size == "smoke":
        config = smoke.smoke_config(config)
    harness.program_config(config, smoke=size == "smoke")
    config["program_fields"] = dict(config["program_fields"], **fields)
    with pytest.raises(harness.Failure, match="qwen3-1.7b") as e:
        harness.program_config(config, smoke=size == "smoke")
    assert named in str(e.value)


@pytest.mark.parametrize("size", ["full", "smoke"])
def test_mamba2_departures_are_refused(size):
    """The program keeps mamba2's residual in bfloat16 and runs RMSNorm at
    1e-6: the configuration's published settings refuse it, at full size
    and at the smoke size alike."""
    config = _config("mamba2-2.7b")
    if size == "smoke":
        config = smoke.smoke_config(config)
    with pytest.raises(harness.Failure) as e:
        harness.program_config(config, smoke=size == "smoke")
    msg = str(e.value)
    assert "'norm_eps': (1e-06, 1e-05)" in msg
    assert "'residual_in_fp32': (None, True)" in msg


@pytest.mark.parametrize("cell, seed", list(PARENT_TRAFFIC))
def test_qwen3_traffic_as_before(cell, seed):
    """Where the published vocabulary is the program's whole table, the
    prompts are those the parent tree drew over the program's rows, byte
    for byte."""
    from repro.configs import get_config
    files = harness.resolve(cell)
    driver = _driver(files)
    assert driver.vocab == get_config(files["config"]["program"]).vocab
    t = traffic_lib.Traffic(files["mix"], seed, driver.vocab)
    h = hashlib.sha256()
    for b in [t.warm()] + [t.batch(i)
                           for i in range(2 * files["mix"]["levels"])]:
        h.update(b.prompts.tobytes())
        h.update(repr(b.new_tokens).encode())
    assert h.hexdigest()[:16] == PARENT_TRAFFIC[cell, seed]
