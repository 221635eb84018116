"""bench/state.py: a rollback that gives back other recurrent state is a
mismatch, and a session that keeps none gets no recurrent field.

Sessions are built through the program's ``Engine`` on the registry's tiny
configurations: ``jamba-1.5-large-398b-tiny`` keeps Mamba state in its
``stage<i>/<tag>`` extras, ``olmo-1b-tiny`` keeps none."""
import jax
import numpy as np
import pytest

from bench import state as bstate


def _sandbox(arch):
    from repro.configs import get_config
    from repro.core import DeltaFS, Sandbox
    from repro.models import Model
    from repro.serve import Engine, PagePool

    cfg = get_config(arch)
    model = Model(cfg)
    pool = PagePool(cfg, num_pages=32, page_size=8, max_pages_per_session=8)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), pool)
    sess = eng.new_session(list(range(1, 12)))
    eng.step([sess])
    return Sandbox(DeltaFS(chunk_bytes=256), sess)


@pytest.fixture(scope="module")
def hybrid():
    return _sandbox("jamba-1.5-large-398b-tiny")


def _ssm_key(sess):
    keys = sorted(k for k, v in bstate.recurrent_extras(sess).items() if "ssm" in v)
    assert keys, "the hybrid session keeps no Mamba state"
    return keys[0]


@pytest.mark.parametrize("changed", [False, True])
def test_recurrent_state_is_compared(hybrid, changed):
    sess = hybrid.proc
    key = _ssm_key(sess)
    kept = sess.extras[key]
    log = bstate.StateLog()
    log.checkpoint("c", hybrid)
    assert "recurrent" in log.at_checkpoint["c"]
    try:
        if changed:
            ssm = np.array(kept["ssm"], copy=True)
            ssm.reshape(-1)[ssm.size // 2] += 1.0
            sess.extras[key] = dict(kept, ssm=jax.numpy.asarray(ssm))
        log.rollback("c", "fast", hybrid)
    finally:
        sess.extras[key] = kept
    compared, bad, notes = log.mismatches()
    assert compared == 1
    if changed:
        assert bad == 1 and notes == ["rollback to c (fast) differs in recurrent"]
    else:
        assert bad == 0 and notes == []


def test_recurrent_checksum_ignores_key_order(hybrid):
    """A slow restore rebuilds each state dict from the payload, in the
    payload's key order: the checksum is the same."""
    sess = hybrid.proc
    key = _ssm_key(sess)
    kept = sess.extras[key]
    want = int(bstate.recurrent_checksum(sess))
    try:
        sess.extras[key] = {k: np.asarray(kept[k]) for k in reversed(sorted(kept))}
        assert int(bstate.recurrent_checksum(sess)) == want
    finally:
        sess.extras[key] = kept


def test_dense_session_has_no_recurrent_field():
    sandbox = _sandbox("olmo-1b-tiny")
    assert bstate.recurrent_extras(sandbox.proc) == {}
    assert bstate.recurrent_checksum(sandbox.proc) is None
    rec = bstate.snapshot(sandbox)
    assert "recurrent" not in rec and "kv" in rec
    log = bstate.StateLog()
    log.checkpoint("c", sandbox)
    log.rollback("c", "fast", sandbox)
    assert log.mismatches() == (1, 0, [])
