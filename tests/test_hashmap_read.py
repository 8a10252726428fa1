"""The hashmap's get through `read_stacked`: one slot gathered from the
stacked replicas answers what `apply_read` answers on the sliced replica,
for every opcode, key and replica; `NodeReplicated` and `ServeFrontend`
answer it as a Python int, as `HashmapOracle` does."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from node_replication_tpu import NodeReplicated
from node_replication_tpu.models import HM_GET, HM_PUT, HM_REMOVE, make_hashmap
from node_replication_tpu.models.hashmap import ABSENT
from node_replication_tpu.ops.encoding import apply_read
from node_replication_tpu.sim.oracle import HashmapOracle

K, R = 64, 4
SEED = 2**40 + 27

# every key's slot is present on two replicas and absent on the other two
KEYS = {
    "in_range": [0, 5, 17, K - 1],
    "past_k": [K, K + 5, 3 * K + 17, 2**31 - 1],
    "negative": [-1, -5, -K, -(2**31)],
}


def stacked_states() -> dict:
    """R replicas holding different values and different presence."""
    rng = np.random.default_rng(SEED)
    slots = np.arange(K)[None, :] + np.arange(R)[:, None]
    return {
        "values": jnp.asarray(rng.integers(0, 2**31, (R, K)), jnp.int32),
        "present": jnp.asarray(slots % 2 == 0),
    }


@pytest.mark.parametrize("kind", sorted(KEYS))
@pytest.mark.parametrize("opcode", [-1, 0, HM_GET, 2, 7])
def test_read_stacked_matches_apply_read_on_the_replica(opcode, kind):
    d = make_hashmap(K)
    states = stacked_states()
    stacked = jax.jit(d.read_stacked)
    sliced = jax.jit(lambda s, rid, o, a: apply_read(
        d, jax.tree.map(lambda x: x[rid], s), o, a))
    seen = set()
    for rid in range(R):
        for k in KEYS[kind]:
            a = (jnp.int32(rid), jnp.int32(opcode), jnp.asarray([k, 0, 0], jnp.int32))
            got, want = stacked(states, *a), sliced(states, *a)
            assert got.shape == () and got.dtype == jnp.int32
            assert int(got) == int(want), (rid, k)
            seen.add(int(got))
    if opcode == HM_GET:  # absent and present slots both answered
        assert ABSENT in seen and len(seen) > 1
    else:
        assert seen == {0}


def test_execute_matches_the_oracle():
    """Puts and removes through every replica, then every key read on
    every replica, synced and stale: the oracle's answer, as an int."""
    nr = NodeReplicated(make_hashmap(K), n_replicas=R)
    toks = [nr.register(r) for r in range(R)]
    orc = HashmapOracle(K)
    rng = np.random.default_rng(SEED)
    for i in range(32):
        k = int(rng.integers(-2 * K, 3 * K))
        op = ((HM_PUT, k, int(rng.integers(-2**31, 2**31))) if rng.random() < 0.7
              else (HM_REMOVE, k))
        assert nr.execute_mut(op, toks[i % R]) == orc.apply(op)
    assert (orc.present.any() and not orc.present.all())
    for tok in toks:
        for k in range(-3, K + 3):
            want = orc.read((HM_GET, k))
            got = (nr.execute((HM_GET, k), tok), nr.execute_stale((HM_GET, k), tok),
                   nr.execute_stale_bounded((HM_GET, k), tok, 0)[0])
            assert all(type(g) is int for g in got) and got == (want,) * 3, k
        for opcode in (0, 2, 7, -1):
            assert nr.execute((opcode, 5), tok) == 0


def test_served_get_answers_an_int():
    from node_replication_tpu.serve import ServeConfig, ServeFrontend

    nr = NodeReplicated(make_hashmap(K), n_replicas=2)
    with ServeFrontend(nr, ServeConfig(queue_depth=8, batch_max_ops=4)) as fe:
        assert fe.submit((HM_PUT, 9, 123), rid=0).result(30) == 0
        got, absent = fe.read((HM_GET, 9), rid=1), fe.read((HM_GET, 10), rid=1)
    assert type(got) is int and got == 123
    assert type(absent) is int and absent == ABSENT
