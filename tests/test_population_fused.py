"""Scan-fused cohort cycles: equivalence with the eager per-step path,
party-axis mesh sharding, bucketed subset distillation, the one-transfer
publish export, and the Continuum's verify-on-fetch memo."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.continuum import Continuum
from repro.core.vault import ModelCard
from repro.launch.mesh import make_party_mesh
from repro.models.small import make_lr, make_mlp
from repro.runtime.population import CohortState, PartyPopulation, stack_teachers
from repro.sharding.rules import party_mesh_size

N_PARTIES, N_PER, N_FEAT, N_CLASSES = 6, 64, 8, 4


def _data(seed=0, n_parties=N_PARTIES):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(N_FEAT, N_CLASSES)).astype(np.float32)
    x = rng.normal(size=(n_parties, N_PER, N_FEAT)).astype(np.float32)
    y = (x @ w).argmax(-1).astype(np.int32)
    return x, y


def _pop(fused, mesh=None, seed=0, model=None, n_parties=N_PARTIES):
    x, y = _data(n_parties=n_parties)
    model = model or make_lr(num_features=N_FEAT, num_classes=N_CLASSES)
    return PartyPopulation(model, x, y, task="t", lr=0.1, batch_size=16,
                           seed=seed, fused=fused, mesh=mesh)


def _leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


def _assert_close(t1, t2, atol=1e-5):
    for a, b in zip(_leaves(t1), _leaves(t2)):
        np.testing.assert_allclose(a, b, atol=atol, rtol=0)


# -- fused == eager equivalence ----------------------------------------------


def test_train_fused_matches_eager():
    f, e = _pop(fused=True), _pop(fused=False)
    lf = [f.train_epochs(2) for _ in range(2)]
    le = [e.train_epochs(2) for _ in range(2)]
    np.testing.assert_allclose(lf, le, atol=1e-5)
    _assert_close(f.params, e.params)


def test_distill_from_fused_matches_eager():
    f, e = _pop(fused=True), _pop(fused=False)
    teacher = f.party_params(0)
    lf = f.distill_from(teacher, epochs=2)
    le = e.distill_from(teacher, epochs=2)
    assert abs(lf - le) < 1e-5
    _assert_close(f.params, e.params)


def test_distill_batch_fused_matches_eager_and_leaves_rest_untouched():
    f, e = _pop(fused=True), _pop(fused=False)
    # numpy snapshots: the fused cycle donates the old param buffers
    before_f = jax.tree_util.tree_map(np.asarray, f.params)
    before_e = jax.tree_util.tree_map(np.asarray, e.params)
    idx = [0, 2, 5]  # odd-size subset exercises bucket padding
    teachers = stack_teachers([f.party_params(1)] * len(idx))
    lf = f.distill_batch(idx, teachers, epochs=2)
    le = e.distill_batch(idx, teachers, epochs=2)
    assert abs(lf - le) < 1e-5
    _assert_close(f.params, e.params)
    untouched = [i for i in range(N_PARTIES) if i not in idx]
    for i in untouched:
        for a, b in zip(_leaves(jax.tree_util.tree_map(
                lambda t: t[i], before_f)), _leaves(f.party_params(i))):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(_leaves(jax.tree_util.tree_map(
                lambda t: t[i], before_e)), _leaves(e.party_params(i))):
            np.testing.assert_array_equal(a, b)


def test_distill_batch_empty_is_noop():
    f = _pop(fused=True)
    assert f.distill_batch([], None) == 0.0


def test_evaluate_fused_matches_host_reference():
    f = _pop(fused=True)
    x, _ = _data(seed=3)
    ex, ey = x[0], np.zeros(N_PER, np.int32)
    accs = f.evaluate(ex, ey)
    logits = f._vapply(f.params, jnp.asarray(ex))
    preds = np.asarray(jnp.argmax(logits, -1))
    ref = (preds == ey[None, :]).mean(axis=1)
    assert accs.shape == (N_PARTIES,)
    np.testing.assert_array_equal(accs, ref)


# -- cohort state + publish export -------------------------------------------


def test_cohort_state_is_device_resident_pytree():
    f = _pop(fused=True)
    assert isinstance(f.state, CohortState)
    leaves = jax.tree_util.tree_leaves(f.state)
    assert all(isinstance(a, (jax.Array, int)) for a in leaves)
    f.train_epochs(1)
    assert f.state.cursor > 0  # cycle advanced the batch cursor


def test_all_party_params_matches_per_party_export():
    f = _pop(fused=True)
    f.train_epochs(1)
    exported = f.all_party_params()
    assert len(exported) == N_PARTIES
    for i in range(N_PARTIES):
        for a, b in zip(_leaves(exported[i]), _leaves(f.party_params(i))):
            np.testing.assert_array_equal(a, b)


# -- mesh sharding ------------------------------------------------------------


def test_single_device_mesh_is_bit_identical():
    meshed = _pop(fused=True, mesh=make_party_mesh())
    plain = _pop(fused=True, mesh=None)
    lm = meshed.train_epochs(2)
    lp = plain.train_epochs(2)
    assert lm == lp
    for a, b in zip(_leaves(meshed.params), _leaves(plain.params)):
        np.testing.assert_array_equal(a, b)
    teachers = stack_teachers([meshed.party_params(1)] * 3)
    lm = meshed.distill_batch([0, 2, 4], teachers)
    lp = plain.distill_batch([0, 2, 4], teachers)
    assert lm == lp
    for a, b in zip(_leaves(meshed.params), _leaves(plain.params)):
        np.testing.assert_array_equal(a, b)


def test_party_mesh_capability_gate():
    assert party_mesh_size(None) == 1
    assert party_mesh_size(make_party_mesh()) == jax.local_device_count()


def test_mesh_pads_party_axis_to_device_multiple():
    # 6 parties on a 1-device mesh need no padding; the padded count is
    # always a device multiple and public views never include pad rows
    f = _pop(fused=True, mesh=make_party_mesh())
    assert f._k % party_mesh_size(f.mesh) == 0
    assert f.num_parties == N_PARTIES
    assert f.evaluate(_data()[0][0], np.zeros(N_PER, np.int32)).shape == (
        N_PARTIES,
    )


MULTI_DEVICE_SCRIPT = """
import numpy as np, jax
from repro.launch.mesh import make_party_mesh
from repro.models.small import make_lr
from repro.runtime.population import PartyPopulation

rng = np.random.default_rng(0)
w = rng.normal(size=(8, 4)).astype(np.float32)
x = rng.normal(size=(6, 64, 8)).astype(np.float32)
y = (x @ w).argmax(-1).astype(np.int32)
assert jax.local_device_count() == 4
model = make_lr(num_features=8, num_classes=4)
kw = dict(task="t", lr=0.1, batch_size=16, seed=0, fused=True)
meshed = PartyPopulation(model, x, y, mesh=make_party_mesh(), **kw)
plain = PartyPopulation(model, x, y, mesh=None, **kw)
assert meshed._k % 4 == 0
lm, lp = meshed.train_epochs(2), plain.train_epochs(2)
assert abs(lm - lp) < 1e-5, (lm, lp)
for i in range(6):  # the padded stack differs; the party views must not
    for a, b in zip(jax.tree_util.tree_leaves(meshed.party_params(i)),
                    jax.tree_util.tree_leaves(plain.party_params(i))):
        np.testing.assert_allclose(a, b, atol=1e-5)
print("OK")
"""


@pytest.mark.slow
def test_multi_device_mesh_matches_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


# -- verify-on-fetch memo -----------------------------------------------------


def _verify_card(acc=0.9):
    return ModelCard(model_id="m1", task="t", arch="lr", owner="p1",
                     num_params=36, metrics={"accuracy": acc})


def test_verify_memo_evaluates_identical_delivery_once():
    calls = []

    def verifier(params, card):
        calls.append(card.model_id)
        return 0.9

    cont = Continuum(verifier=verifier)
    model = make_lr(num_features=8, num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    card = _verify_card()
    assert cont._check_fraud(params, card) == (False, 0.9, 0.9)
    assert cont._check_fraud(params, card) == (False, 0.9, 0.9)
    assert len(calls) == 1  # second delivery of the same bytes: memo hit


def test_verify_memo_does_not_mask_tampered_blobs():
    def verifier(params, card):
        # an honest eval: the tampered (zeroed) weights score nothing
        total = sum(float(jnp.abs(leaf).sum())
                    for leaf in jax.tree_util.tree_leaves(params))
        return 0.9 if total > 0 else 0.0

    cont = Continuum(verifier=verifier)
    model = make_lr(num_features=8, num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    card = _verify_card(acc=0.9)
    fraud, _, measured = cont._check_fraud(params, card)
    assert not fraud and measured == 0.9
    tampered = jax.tree_util.tree_map(jnp.zeros_like, params)
    fraud, claimed, measured = cont._check_fraud(tampered, card)
    assert fraud  # different bytes -> memo miss -> honest re-measurement
    assert claimed == 0.9 and measured == 0.0


def test_verify_memo_cleared_on_verifier_swap():
    cont = Continuum(verifier=lambda p, c: 0.9)
    model = make_lr(num_features=8, num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    card = _verify_card(acc=0.9)
    assert cont._check_fraud(params, card) == (False, 0.9, 0.9)
    cont.verifier = lambda p, c: 0.0  # new eval set: old memo must not leak
    fraud, _, measured = cont._check_fraud(params, card)
    assert fraud and measured == 0.0


# -- cross-architecture sanity ------------------------------------------------


def test_fused_paths_work_for_mlp_cohorts():
    model = make_mlp(num_features=N_FEAT, num_classes=N_CLASSES, hidden=16)
    f = _pop(fused=True, model=model)
    e = _pop(fused=False, model=model)
    np.testing.assert_allclose(f.train_epochs(1), e.train_epochs(1),
                               atol=1e-5)
    _assert_close(f.params, e.params, atol=1e-5)
