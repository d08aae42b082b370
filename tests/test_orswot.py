"""Orswot tests — mirrors `/root/reference/test/orswot.rs` and the in-module
suite `/root/reference/src/orswot.rs:246-355`.

Covers: convergence under interleavings across 2..10 simulated replicas
(`test/orswot.rs:36-77`), the riak_dt-ported regressions, deferred-remove
preservation, and reset-remove semantics via Map (`test/orswot.rs:270-307`).
"""

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from crdt_tpu import Dot, Map, Orswot, RmCtx, VClock
from crdt_tpu.scalar.orswot import Add, Rm

ACTOR_MAX = 11

op_prims = st.lists(
    st.tuples(
        st.integers(0, 255),  # actor
        st.integers(0, 255),  # member
        st.integers(0, 255),  # choice
        st.integers(0, 2**64 - 1),  # counter
    ),
    max_size=20,
)


def build_opvec(prims):
    """`test/orswot.rs:14-34`: alternate Add/Rm ops from primitive tuples."""
    ops = []
    for actor, member, choice, counter in prims:
        if choice % 2 == 0:
            op = Add(dot=Dot(actor, counter), member=member)
        else:
            op = Rm(clock=Dot(actor, counter).to_vclock(), member=member)
        ops.append((actor, op))
    return ops


@given(op_prims)
def test_prop_merge_converges(prims):
    """`test/orswot.rs:37-76`: route each op to witnesses[actor % i] for
    every cluster size i in 2..11; all merged results must be identical."""
    ops = build_opvec(prims)
    result = None
    for i in range(2, ACTOR_MAX):
        witnesses = [Orswot() for _ in range(i)]
        for actor, op in ops:
            witnesses[actor % i].apply(op)
        merged = Orswot()
        for witness in witnesses:
            merged.merge(witness)
        # defer_plunger flushes deferred elements (`test/orswot.rs:61-62`)
        merged.merge(Orswot())
        if result is not None:
            assert result == merged, f"diverged at cluster size {i}"
        else:
            result = merged


def test_weird_highlight_1():
    """`test/orswot.rs:83-92`: identical clocks with different elements drop
    the non-common elements — don't reuse a witness across copies."""
    a, b = Orswot(), Orswot()
    op_a = a.add(1, a.value().derive_add_ctx(1))
    op_b = b.add(2, b.value().derive_add_ctx(1))
    a.apply(op_a)
    b.apply(op_b)
    a.merge(b)
    assert a.value().val == set()


def test_adds_dont_destroy_causality():
    """`test/orswot.rs:95-133`."""
    a = Orswot()
    b = a.clone()
    c = a.clone()

    c_ctx = c.value()
    c.apply(c.add("element", c_ctx.derive_add_ctx(1)))
    c.apply(c.add("element", c_ctx.derive_add_ctx(2)))

    c_element_ctx = c.contains("element")
    # the remove context should descend from vclock {1->1, 2->1}
    assert c_element_ctx.rm_clock == VClock.from_iter([(1, 1), (2, 1)])

    a_add_ctx = a.value().derive_add_ctx(7)
    a.apply(a.add("element", a_add_ctx))
    b.apply(c.remove("element", c_element_ctx.derive_rm_ctx()))

    a.apply(a.add("element", a.value().derive_add_ctx(1)))

    a.merge(b)
    assert a.value().val == {"element"}


def test_merge_clocks_of_identical_entries():
    """`test/orswot.rs:138-160`: identical entries with different clocks are
    merged, not removed."""
    a = Orswot()
    b = a.clone()
    a.apply(a.add(1, a.value().derive_add_ctx(3)))
    b.apply(b.add(1, b.value().derive_add_ctx(7)))
    a.merge(b)
    assert a.value().val == {1}
    final_clock = VClock.from_iter([(3, 1), (7, 1)])
    read_ctx = a.contains(1)
    assert read_ctx.val is True
    assert read_ctx.rm_clock == final_clock


def test_disjoint_merge():
    """`test/orswot.rs:163-188` (riak_dt port)."""
    a = Orswot()
    b = a.clone()

    a.apply(a.add(0, a.value().derive_add_ctx(1)))
    assert a.value().val == {0}

    b.apply(b.add(1, b.value().derive_add_ctx(2)))
    assert b.value().val == {1}

    c = a.clone()
    c.merge(b)
    assert c.value().val == {0, 1}

    a.apply(a.remove(0, a.contains(0).derive_rm_ctx()))
    d = a.clone()
    d.merge(c)
    assert d.value().val == {1}


def test_no_dots_left():
    """`test/orswot.rs:193-230` (riak_dt EQC port): dropping dots in merge
    is not enough if the value is then stored with an empty clock."""
    a, b = Orswot(), Orswot()
    a.apply(a.add(0, a.value().derive_add_ctx(1)))
    b.apply(b.add(0, b.value().derive_add_ctx(2)))
    c = a.clone()
    a.apply(a.remove(0, a.contains(0).derive_rm_ctx()))

    # replicate B to A, now A has B's entry
    a.merge(b)
    assert a.value().val == {0}
    assert a.value().add_clock == VClock.from_iter([(1, 1), (2, 1)])

    b.apply(b.remove(0, b.contains(0).derive_rm_ctx()))
    assert b.value().val == set()

    # replicate C to B, now B has A's old entry
    b.merge(c)
    assert b.value().val == {0}

    # merge everything: no entry must survive with no dots
    b.merge(a)
    b.merge(c)
    assert b.value().val == set()


def test_dead_node_update():
    """`test/orswot.rs:245-267`: remove at a with a context obtained from a
    node that then goes down forever."""
    a = Orswot()
    a_op = a.add(0, a.value().derive_add_ctx(1))
    assert a_op == Add(dot=Dot(1, 1), member=0)
    a.apply(a_op)
    assert a.contains(0).rm_clock == Dot(1, 1).to_vclock()

    b = a.clone()
    b.apply(b.add(1, b.value().derive_add_ctx(2)))
    bctx = b.value()
    assert bctx.add_clock == VClock.from_iter([(1, 1), (2, 1)])
    rm_op = a.remove(0, bctx.derive_rm_ctx())
    a.apply(rm_op)
    assert a.value().val == set()


def test_reset_remove_semantics():
    """`test/orswot.rs:270-307`: reset-remove via Map<u8, Orswot>."""
    m1 = Map(Orswot)

    op1 = m1.update(101, m1.get(101).derive_add_ctx(75), lambda s, ctx: s.add(1, ctx))
    m1.apply(op1)

    m2 = m1.clone()

    read_ctx = m1.get(101)
    op2 = m1.rm(101, read_ctx.derive_rm_ctx())
    m1.apply(op2)
    op3 = m2.update(101, m2.get(101).derive_add_ctx(93), lambda s, ctx: s.add(2, ctx))
    m2.apply(op3)

    assert m1.get(101).val is None
    assert m2.get(101).val.value().val == {1, 2}

    snapshot = m1.clone()
    m1.merge(m2)
    m2.merge(snapshot)

    assert m1 == m2
    assert m1.get(101).val.value().val == {2}


# -- in-module regressions (`src/orswot.rs:246-355`) ------------------------


def test_ensure_deferred_merges():
    """`src/orswot.rs:251-282`: deferred operations must be carried over
    after a merge."""
    a, b = Orswot(), Orswot()

    b_read_ctx = b.value()
    b.apply(b.add("element 1", b_read_ctx.derive_add_ctx(5)))

    # remove with a future context
    b.apply(b.remove("element 1", RmCtx(clock=Dot(5, 4).to_vclock())))

    a_read_ctx = a.value()
    a.apply(a.add("element 4", a_read_ctx.derive_add_ctx(6)))

    # remove with a future context
    b.apply(b.remove("element 9", RmCtx(clock=Dot(4, 4).to_vclock())))

    merged = Orswot()
    merged.merge(a)
    merged.merge(b)
    merged.merge(Orswot())
    assert len(merged.deferred) == 2


def test_preserve_deferred_across_merges():
    """`src/orswot.rs:286-315`: deferred removals survive merges."""
    a = Orswot()
    b = a.clone()
    c = a.clone()

    # add element 5 from witness 1
    a.apply(a.add(5, a.value().derive_add_ctx(1)))

    # remove 5 with an advanced clock for witnesses 1 and 4
    vc = VClock.from_iter([(1, 3), (4, 8)])

    # remove from b (has not yet seen the add for 5) with advanced ctx
    b.apply(b.remove(5, RmCtx(clock=vc)))
    assert len(b.deferred) == 1

    # deferred elements survive a merge
    c.merge(b)
    assert len(c.deferred) == 1

    # merging the deferred set with one containing an inferior member hides
    # the member and keeps the deferred info
    a.merge(c)
    assert a.value().val == set()


def test_present_but_removed():
    """`src/orswot.rs:320-354` (riak_dt EQC port): dots must be dropped in
    merge when an element is present in both sets."""
    a, b = Orswot(), Orswot()
    a.apply(a.add(0, a.value().derive_add_ctx("A")))
    # replicate to C so A has 0->{a, 1}
    c = a.clone()

    a.apply(a.remove(0, a.contains(0).derive_rm_ctx()))
    assert len(a.deferred) == 0

    b.apply(b.add(0, b.value().derive_add_ctx("B")))

    # replicate B to A: A has a 0 with dot {b,1} and clock [{a,1},{b,1}]
    a.merge(b)

    b.apply(b.remove(0, b.contains(0).derive_rm_ctx()))
    # both C and A have a 0, but after the merges it must be gone: C's was
    # removed by A's remove, and A's by B's remove.
    a.merge(b)
    a.merge(c)
    assert a.value().val == set()


class TestFoldMergeTree:
    """fold_merge_fleets vs the sequential left fold.

    The ORSWOT join is associative in its *observable* state — value(),
    set clock, member table — which is the CRDT convergence guarantee.
    The dot tables are NOT bit-associative in the reference semantics:
    the only-in-self rule keeps the member's FULL clock when any dot is
    novel (`orswot.rs:94-103`), so which dominated lanes survive depends
    on which partner's clock was present at that pairing, and
    apply_deferred subtracts during every intermediate merge
    (`orswot.rs:195-211,235-243`).  The scalar engine reproduces both
    effects, so the contract tested here is: order-independent pieces
    bit-equal vs the sequential fold, and the full state bit-faithful to
    the SCALAR engine folding in the same tree order."""

    def _fleets(self, rng, n, a, m, d, r, deferred_frac):
        import jax.numpy as jnp

        from crdt_tpu.utils.testdata import anti_entropy_fleets

        fleets = anti_entropy_fleets(
            rng, n, a, m, d, r, base=4, novel=1, deferred_frac=deferred_frac
        )
        return tuple(
            jnp.stack([jnp.asarray(rep[k]) for rep in fleets]) for k in range(5)
        )

    @staticmethod
    def _seq_fold(stacked, r, m, d):
        from crdt_tpu.ops import orswot_ops

        acc = tuple(x[0] for x in stacked)
        for i in range(1, r):
            acc = orswot_ops.merge(*acc, *(x[i] for x in stacked), m, d)[:5]
        return orswot_ops.merge(*acc, *acc, m, d)[:5]

    @pytest.mark.parametrize("deferred_frac", [0.0, 0.5])
    @pytest.mark.parametrize("r", [2, 3, 5, 8])
    def test_tree_fold_parity(self, r, deferred_frac):
        import numpy as np

        from crdt_tpu.ops import orswot_ops
        from crdt_tpu.scalar.orswot import Orswot
        from crdt_tpu.utils.testdata import dense_row_to_scalar

        rng = np.random.RandomState(100 + r)
        n, a, m, d = 17, 8, 5 + r, 3
        stacked = self._fleets(rng, n, a, m, d, r, deferred_frac)
        acc = self._seq_fold(stacked, r, m, d)
        got = orswot_ops.fold_merge_fleets(
            [tuple(x[i] for x in stacked) for i in range(r)], m, d)[:5]

        # order-independent pieces: set clock and canonical member table
        assert np.array_equal(np.asarray(got[0]), np.asarray(acc[0]))
        assert np.array_equal(np.asarray(got[1]), np.asarray(acc[1]))

        # full state must be bit-faithful to the scalar engine folding in
        # the same tree order (evens-with-odds, odd fleet carries)
        for obj in range(n):
            lvl = [
                dense_row_to_scalar(*(np.asarray(x[i, obj]) for x in stacked))
                for i in range(r)
            ]
            while len(lvl) > 1:
                nxt = []
                for i in range(0, len(lvl) - 1, 2):
                    lvl[i].merge(lvl[i + 1])
                    nxt.append(lvl[i])
                if len(lvl) % 2:
                    nxt.append(lvl[-1])
                lvl = nxt
            oracle = lvl[0]
            oracle.merge(Orswot())

            want = {
                mid: {
                    i: int(c)
                    for i, c in enumerate(np.asarray(got[2][obj][s]))
                    if int(c)
                }
                for s, mid in enumerate(int(x) for x in np.asarray(got[1][obj]))
                if mid != -1
            }
            have = {k: dict(v.dots) for k, v in oracle.entries.items()}
            assert want == have, f"object {obj}: dense tree != scalar tree"

    def test_overflow_flag_propagates(self):
        import numpy as np

        from crdt_tpu.ops import orswot_ops
        from crdt_tpu.utils.testdata import random_orswot_arrays

        import jax.numpy as jnp

        rng = np.random.RandomState(7)
        # disjoint member universes force m_cap overflow somewhere in the tree
        reps = []
        for i in range(4):
            arrs = list(random_orswot_arrays(rng, 16, 4, 4, 2))
            ids = np.asarray(arrs[1])
            ids = np.where(ids != -1, ids + 100 * i, ids)
            arrs[1] = ids
            reps.append(tuple(jnp.asarray(x) for x in arrs))
        out = orswot_ops.fold_merge_fleets(reps, 2, 2)
        assert bool(np.asarray(out[5]).any()), "tree fold must surface overflow"


@pytest.mark.parametrize("n_chunks", [4, 6])
def test_salted_scan_matches_stepped_replay(n_chunks):
    """Chunk folds streamed through a salted ``lax.scan`` (one dispatch)
    equal the same salt chain replayed as per-step dispatches XLA cannot
    hoist across: if the scan's while-loop were invariant-hoisted or
    partially dead-code-eliminated into computing fewer folds, the
    data-dependent salts would diverge and the replay would not match."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax

    from crdt_tpu.ops import orswot_ops
    from crdt_tpu.utils.testdata import anti_entropy_fleets

    rng = np.random.RandomState(2)
    chunk, a, m, d, r = 64, 8, 8, 2, 4

    templates = []
    for _ in range(2):
        reps = anti_entropy_fleets(
            rng, chunk, a, m, d, r, base=3, novel=1, deferred_frac=0.25,
        )
        templates.append(
            tuple(jnp.stack([rep[k] for rep in reps]) for k in range(5))
        )
    t0_, t1_ = templates

    def fold_join(stack):
        acc = tuple(x[0] for x in stack)
        for i in range(1, r):
            acc = orswot_ops.merge(*acc, *(x[i] for x in stack), m, d)[:5]
        return orswot_ops.merge(*acc, *acc, m, d)[:5]  # defer plunger

    def salted_fold(tpl, salt):
        return fold_join((tpl[0] ^ salt,) + tpl[1:])

    def next_salt(acc):
        # max-reduce the DOTS plane: keeps the expensive member pipeline
        # live under DCE
        return (jnp.max(acc[2]) & jnp.uint32(7)) | jnp.uint32(1)

    @jax.jit
    def run_chunks(t0_, t1_):
        def body(carry, _):
            salt, _prev = carry
            o0 = salted_fold(t0_, salt)
            o1 = salted_fold(t1_, next_salt(o0))
            return (next_salt(o1), o1), None

        init = (jnp.uint32(1), tuple(x[0] for x in t0_))
        (_salt, out), _ = lax.scan(body, init, None, length=n_chunks // 2)
        return out

    scan_out = run_chunks(t0_, t1_)

    # per-step replay: separately compiled programs, same salt chain
    sf = jax.jit(salted_fold)
    ns = jax.jit(next_salt)
    salt = jnp.uint32(1)
    out = None
    for _ in range(n_chunks // 2):
        o0 = sf(t0_, salt)
        o1 = sf(t1_, ns(o0))
        salt = ns(o1)
        out = o1

    for i, (g, w) in enumerate(zip(scan_out, out)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"plane {i}: scan diverged from per-step replay",
        )



@given(op_prims)
@settings(max_examples=20, deadline=None)
def test_prop_batch_merge_converges(prims):
    """The device engine passes the same interleaving search as the scalar
    one (`test/orswot.rs:37-76` tier-2 idiom): route each op to
    ``witnesses[actor % i]``, pack every witness as a batch row, join with
    the batched merge + defer plunger — identical for every cluster size,
    and equal to the scalar N-way join."""
    from crdt_tpu.batch import OrswotBatch
    from crdt_tpu.config import CrdtConfig
    from crdt_tpu.utils.interning import Universe

    ops = build_opvec(prims)
    uni = Universe(CrdtConfig(num_actors=32, member_capacity=24,
                              deferred_capacity=24))
    result = None
    for i in (2, 5, 10):
        witnesses = [Orswot() for _ in range(i)]
        for actor, op in ops:
            witnesses[actor % i].apply(op)
        acc = OrswotBatch.from_scalar([witnesses[0]], uni)
        for w in witnesses[1:]:
            acc = acc.merge(OrswotBatch.from_scalar([w], uni))
        acc = acc.merge(OrswotBatch.zeros(1, uni))  # defer plunger
        merged = acc.to_scalar(uni)[0]
        if result is None:
            result = merged
            # cross-engine: the scalar fold at this cluster size agrees
            scalar = Orswot()
            for w in witnesses:
                scalar.merge(w)
            scalar.merge(Orswot())
            assert merged == scalar, "batch fold != scalar fold"
        else:
            assert result == merged, f"batch fold diverged at cluster size {i}"
