"""The scan's kernel pair (``ops/ssd.py``: ``ssd_chunk_fwd``,
``ssd_chunk_bwd``) against the bits of its parent: since PR 69 ``x``,
``dy``, ``y`` and ``dx`` cross HBM channels-last and no block is turned
round in VMEM, and a turn is exact, so ``y`` and every cotangent must be the
parent's bit for bit — at the two state-space cells' head blocks, on a
sequence the chunk does not divide, and once per shard of a two-device mesh.

A file of its own and not four more cases of ``tests/test_llama_ssm.py``,
whose operands it borrows: under the driver's ``--dist loadfile`` a file is
one worker's serial work, and that file is tier-1's longest (some 800 s of a
1,470 s limit).
"""

import contextlib
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_llama_ssm import B, F32, KERNELS, _rel, _scan_operands

from dlrover_tpu.ops import ssd
from dlrover_tpu.parallel.mesh import MeshSpec, build_mesh


@contextlib.contextmanager
def _one_primitive_at_a_time():
    """Pallas' interpreter run eagerly.  ``pallas_call``'s own evaluation
    rule compiles the interpreted kernel as ONE XLA program even under
    ``jax.disable_jit``, and XLA's CPU compiler folds a transposition into
    the product beside it (``dot`` with both operands contracted on dim 1,
    operands swapped), which changes the order of a float32 sum: a turn of
    a block moves 1 ulp of a fifth of ``y`` THERE, and on no chip.  One
    primitive at a time, a turn is a turn."""
    from jax._src.pallas import hlo_interpreter, pallas_call

    def evaluate(*args, interpret, backend, **params):
        assert interpret is True
        return hlo_interpreter.pallas_call_hlo_interpret(
            *args, backend=backend, **params)

    compiled = pallas_call.pallas_call_p.impl
    pallas_call.pallas_call_p.def_impl(evaluate)
    try:
        with jax.disable_jit():
            yield
    finally:
        pallas_call.pallas_call_p.def_impl(compiled)


PAIR_RESULTS = ("y", "dx", "ddt", "dcs", "dB", "dC", "dentering", "dD")


def _pair_alone(chunk, groups, heads):
    """``y`` and the seven cotangents of the kernel pair alone, bfloat16
    operands as the cells hand them over, two rows of two chunks."""
    x, dt, a, bm, cm, d = _scan_operands(
        chunk, groups, 64, jnp.bfloat16, heads=heads, chunks=2)
    n = bm.shape[-1]
    dtc = dt.reshape(B, 2, chunk, groups, heads)
    ops = (x.reshape(B, 2, chunk, groups, heads, 64), dtc,
           jnp.cumsum(dtc * a.reshape(groups, heads), axis=2),
           bm.reshape(B, 2, chunk, groups, n),
           cm.reshape(B, 2, chunk, groups, n),
           (jax.random.normal(jax.random.PRNGKey(5),
                              (2, B, groups, heads, 64, n)) * .1).astype(
                                  jnp.bfloat16),
           d.reshape(groups, heads))
    dy = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def pair(*ops):
        y, vjp = jax.vjp(lambda *o: ssd.chunk_outputs(*o, **KERNELS), *ops)
        return dict(zip(PAIR_RESULTS, (y,) + vjp(dy)))

    return pair, ops


def _ragged_scan():
    """The scan through the pair on a sequence of one and a half chunks:
    ``y``, the state it leaves and the six gradients."""
    ops = _scan_operands(128, 2, 64, jnp.bfloat16, heads=8, chunks=1.5)
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def loss(*ops):
        y, state, _ = ssd.ssd_chunked(*ops[:5], 128, D=ops[5], **KERNELS)
        return (jnp.sum(y * weights) + jnp.sum(jnp.square(state)),
                (y, state))

    def results(*ops):
        (_, outputs), grads = jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True)(*ops)
        return dict(zip(("y", "state", "dx", "ddt", "dA", "dB", "dC", "dD"),
                        outputs + grads))

    return results, ops


PAIR_CASES = {
    # the Granite cell's head block: one group, chunks of 256, eight heads
    # a grid step (two blocks a group here, eight there)
    "one_group_chunks_of_256": lambda: _pair_alone(256, 1, 16),
    # the one-branch cell's: a group's eight heads, chunks of 128 (two
    # groups here, eight there)
    "groups_of_eight_heads_chunks_of_128": lambda: _pair_alone(128, 2, 8),
    "a_sequence_the_chunk_does_not_divide": _ragged_scan,
}

#: sha256 of each result's bytes as THIS test body computed them with the
#: parent's kernels (commit b0cde6e, PR 68: ``x``, ``y``, ``dy`` and ``dx``
#: crossed HBM positions-last and a block was turned round in VMEM).  A turn
#: is exact, so the channels-last pair (PR 69) gives the same bits.  A later
#: PR that changes the pair's arithmetic on purpose computes them anew on ITS
#: parent and says so.
PARENT_PAIR_SHA256 = {
    "a_sequence_the_chunk_does_not_divide": {
        "y":
            "78c5dc14ad150c3e54b47c8ce008a9c990431d1a7e1554ed2df75b166a989cff",
        "state":
            "a7ff79a9957acec8725ea3253c4668680e27f86b13f04bb1323432eb0a39b158",
        "dx":
            "86b94e74919401db48a4e564acc3569cc9f60f5508c41ab1519ab4cad60f4414",
        "ddt":
            "10e1be9707a349558c8ccf95d1c88ff07e94b2393ee2d73efd3cc8cd88b98ba2",
        "dA":
            "6df5d5f79f5f76006a015de20b43e2af530f42079cb8cf53330fcb268ff8ae89",
        "dB":
            "5016b5991df76e0aed5e559f87efc752f84c779b62bbdb2917c387bbda5bf6a5",
        "dC":
            "9ddfb48bd87cc85de428bf7f1326796e0c6caafdb7ba8b84c0f888ad0262e3b5",
        "dD":
            "c07615b4a64b2e692d5338c503c7d620ee12ce9543ee640304ca89a81b3cde5d",
    },
    "groups_of_eight_heads_chunks_of_128": {
        "y":
            "70ffdfedecb9a23c0c587a4fc4d6171dc53d278d364a944f2740365eef6da6a3",
        "dx":
            "af451bbf9b8d0eb649f482f33b9cf11e883ee01aa36ebea02d0a78d9a7e0211b",
        "ddt":
            "3ee568cec478cca14f9a7e2a5a08f0ad9485e71f60127b2f96a868a906120408",
        "dcs":
            "84a0aec7fcfad8bfc2d0a5e0c595cc036bb82eed567b268023ce29685b56474b",
        "dB":
            "7254a7fd85ac69b772379a109c9c49abc3347574820b649bb41119c659ce9283",
        "dC":
            "d3c8dab8497694f67958d7158b00028379d94571ece9949cb96682ee2e222d57",
        "dentering":
            "ff80ea9b1a08e77e53dbf2f62703873d9be959869c22a94d10cd6345949a73c9",
        "dD":
            "eccf1278e4d44ab0a0ae95b0fd4a1d13e7e6ca731cfc52598a8702b695e7a368",
    },
    "one_group_chunks_of_256": {
        "y":
            "740eaeef5a3e274fb7edf868b1ad0c7ecaaf0c9ff455c59a2ce2221c9064b22f",
        "dx":
            "3a5e54bf1f8e15a3fae4a0acd01a58248a7c085a39d67b66c37c88eab2f1a317",
        "ddt":
            "ec0dadc6142607443edd8aee11c5bc846f295622266c06a97c3b2e9a5c9b4101",
        "dcs":
            "67b0bcac1166af5ac58c40324f6cca63676e2993819aa2d037828395a7ed4894",
        "dB":
            "6499080e449661c41d9cab698d91d41aa9c3c017e05316c6ab9cf12cfebf26c9",
        "dC":
            "692c342417ea8117b225aaf2768f05d10d23f5dc15ed5c8eaba3ac045927b587",
        "dentering":
            "e1815e15a97f4b842f2440dec8bf717738dcf099eef5bfd7d39a2d27f811b7ab",
        "dD":
            "5dc834777c418c6771bfb1fc56dfd48bd49a9a4741eddedb8bb5b90a691ae6ea",
    },
}


@pytest.mark.parametrize("case", sorted(PAIR_CASES))
def test_the_pair_gives_the_parents_bits(case):
    function, ops = PAIR_CASES[case]()
    with _one_primitive_at_a_time():
        results = function(*ops)
    assert results["y"].dtype == F32
    assert results["dx"].dtype == jnp.bfloat16
    got = {name: hashlib.sha256(np.asarray(r).tobytes()).hexdigest()
           for name, r in results.items()}
    assert got == PARENT_PAIR_SHA256[case]


def test_a_two_device_mesh_changes_no_bit_of_a_row():
    """The pair once per batch shard (``ops/per_shard.py``: the specs name
    the batch dim only) against the same compiled function on one device:
    every result with a batch dim bit for bit, ``dD`` — summed over the
    rows in another order — at float32's rounding."""
    pair, ops = _pair_alone(128, 2, 8)
    want = jax.jit(pair)(*ops)
    with jax.set_mesh(build_mesh(MeshSpec(dp=2), jax.devices()[:2])):
        got = jax.jit(pair)(*ops)
    assert not got["y"].sharding.is_fully_replicated
    for name in PAIR_RESULTS[:-1]:
        assert got[name].dtype == want[name].dtype, name
        assert np.array_equal(np.asarray(got[name]),
                              np.asarray(want[name])), name
    assert _rel(got["dD"], want["dD"]) < 1e-6
