"""PagedDecodeEngine's counters (EngineStats) and the pool occupancy they
sit beside (kv.stats()), on a fake serve program and a pool of a few
blocks."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.engine import PagedDecodeEngine, Request

VOCAB = 32


class FakeServe:
    """serve_fn whose greedy token is the slot's last fed token + 1; it
    records each call's width, tokens fed and blocks in use."""

    def __init__(self):
        self.engine = None
        self.calls = []          # (C, n_new summed, blocks in use)

    def __call__(self, tokens, pool, tables, pos, n_new):
        self.calls.append((tokens.shape[1], int(n_new.sum()),
                           self.engine.kv.used_blocks))
        idx = jnp.clip(n_new - 1, 0, tokens.shape[1] - 1)
        last = jnp.take_along_axis(tokens, idx[:, None], axis=1)[:, 0]
        return jax.nn.one_hot((last + 1) % VOCAB, VOCAB), pool


def engine(num_blocks, block_size, batch=2, chunk=4, max_seq=32):
    fn = FakeServe()
    eng = PagedDecodeEngine(fn, lambda nb, bs: None, batch,
                            num_blocks=num_blocks, block_size=block_size,
                            max_seq=max_seq, chunk=chunk)
    fn.engine = eng
    return eng, fn


def test_one_preemption_and_one_deferred_admission():
    """Three blocks of four tokens.  A and B (4-token prompts) fill one
    block each; at their fifth token A takes the third block and B, out
    of blocks, is preempted.  Re-admitting B (prompt + 1 token = 5) needs
    two blocks with one free: deferred once, admitted after A retires."""
    eng, fn = engine(num_blocks=3, block_size=4)
    eng.submit(Request(uid=0, prompt=[1, 2, 3, 4], max_new=3))
    eng.submit(Request(uid=1, prompt=[5, 6, 7, 8], max_new=6))
    done = eng.run_until_drained(max_steps=50)
    assert done.drained and len(done) == 2
    st = eng.stats
    assert (st.preempted, st.admit_deferred) == (1, 1)
    assert st.admitted == 3 and st.truncated == 0
    assert {r.uid: len(r.tokens) for r in done} == {0: 3, 1: 6}
    # the tick and token counters agree with what the program was given
    assert st.ticks == len(fn.calls)
    assert st.ticks_wide == sum(c == eng.chunk for c, _, _ in fn.calls)
    assert st.prefill_tokens + st.decode_tokens == sum(
        n for _, n, _ in fn.calls)


def test_truncation_is_counted():
    eng, _ = engine(num_blocks=4, block_size=4, batch=1, max_seq=8)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new=20))
    done = eng.run_until_drained(max_steps=50)
    assert done[0].truncated
    assert eng.stats.truncated == 1 and eng.stats.preempted == 0


def test_peak_blocks_is_the_most_held_at_once():
    """Blocks grow only while a tick is scheduled and are freed after its
    program ran, so the most held at once is the most any call saw."""
    eng, fn = engine(num_blocks=16, block_size=2, batch=3)
    for uid, (n, m) in enumerate([(3, 6), (7, 2), (1, 9), (5, 4), (2, 3)]):
        eng.submit(Request(uid=uid, prompt=list(range(1, n + 1)),
                           max_new=m))
    assert eng.run_until_drained(max_steps=100).drained
    pool = eng.kv.stats()
    assert pool.used_blocks == 0
    assert pool.peak_blocks == max(b for _, _, b in fn.calls) > 0
    assert eng.stats.preempted == eng.stats.admit_deferred == 0


def test_reshard_keeps_the_counters():
    eng, fn = engine(num_blocks=8, block_size=4)
    eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new=4))
    eng.step()
    before = eng.stats
    eng.reshard(fn, lambda nb, bs: None)
    assert eng.stats is before and before.ticks == 1
    assert eng.run_until_drained(max_steps=20).drained
    assert eng.stats.admitted == 2


def test_blocks_read_counts_the_live_table_blocks():
    """kv_blocks_read sums, over the ticks, the table entries the step was
    given that name a block; kv_blocks_table the working slots' whole
    tables.  Idle slots count in neither."""
    from repro.serve.kv_cache import FREE_BLOCK

    seen = []

    class Recording(FakeServe):
        def __call__(self, tokens, pool, tables, pos, n_new):
            t, n = map(np.asarray, (tables, n_new))
            seen.append(((t != FREE_BLOCK).sum(), (n > 0).sum() * t.shape[1],
                         sum(len(self.engine.kv.blocks_for(uid))
                             for uid in self.engine.kv._tables)))
            return super().__call__(tokens, pool, tables, pos, n_new)

    fn = Recording()
    eng = PagedDecodeEngine(fn, lambda nb, bs: None, 3, num_blocks=16,
                            block_size=2, max_seq=16, chunk=4)
    fn.engine = eng
    for uid, (n, m) in enumerate([(5, 3), (1, 6), (3, 2), (2, 2)]):
        eng.submit(Request(uid=uid, prompt=list(range(1, n + 1)),
                           max_new=m))
    assert eng.run_until_drained(max_steps=50).drained
    st = eng.stats
    assert st.ticks == len(seen) > 3
    assert st.kv_blocks_read == sum(r for r, _, _ in seen)
    assert st.kv_blocks_table == sum(t for _, t, _ in seen)
    # every held block is in some working slot's table
    assert all(r == held for r, _, held in seen)
    assert 0 < st.kv_blocks_read < st.kv_blocks_table
