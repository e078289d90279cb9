"""``BatchedList.apply_chunks``: per-replica op delivery from the device op
log, against the plain index replay of the trace
(``benchmark/reference/list_index.py``), against ``apply_ops`` bit for
bit, and with the state donated; the seeded edit-trace generator the
benchmark cell draws its trace from; and the strict-mode duplicate check
of ``apply_ops``."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from crdt_tpu.config import configured
from crdt_tpu.models import BatchedList
from crdt_tpu.models.list import LANES, _donated, _list_apply_chunks, _scatter_ops
from crdt_tpu.native import DELETE, INSERT
from crdt_tpu.traits import DotRange

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)

from gen import edit_trace  # noqa: E402
from reference import list_index as ref  # noqa: E402

WIDTH = 32


def small_trace(seed, n_ops=640, n_inserts=448, width=WIDTH):
    return edit_trace.generate(seed, n_ops, n_inserts, 0.05, 3.0, 8, width // 8)


def read_all(model):
    return [model.read(r) for r in range(model.n_replicas)]


@pytest.mark.parametrize("seed", [3, (1 << 33) + 1, 2900000011])
def test_lagged_replicas_read_the_prefix_they_applied(seed):
    """Each replica at its own lag applies its chunks in order; after every
    round each one reads the replay's text at the ops it has applied."""
    trace = small_trace(seed)
    kinds = trace[0]
    model = BatchedList.from_trace(*trace, n_replicas=8, chunk_width=WIDTH)
    # The trace exercises both compositions: a character typed and
    # deleted inside one chunk, and a delete in a later chunk than its
    # insert.
    chunk = np.arange(len(kinds)) // WIDTH
    first = {}
    for i, h in enumerate(model.op_handles.tolist()):
        first.setdefault(h, i)
    deletes = np.flatnonzero(kinds == DELETE)
    born = np.asarray([first[model.op_handles[i]] for i in deletes])
    assert (chunk[born] == chunk[deletes]).any()
    assert (chunk[born] < chunk[deletes]).any()

    rng = np.random.default_rng(seed)
    lag = rng.integers(0, 4, model.n_replicas)
    stops = list(range(0, len(kinds), WIDTH)) + [len(kinds)]
    want = ref.texts_at(*trace[:3], stops)
    for t in range(model.n_chunks + lag.max()):
        ids = t - lag
        ids = np.where((ids >= 0) & (ids < model.n_chunks), ids, -1)
        model.apply_chunks(ids)
        done = np.clip(t + 1 - lag, 0, model.n_chunks)
        for r, text in enumerate(read_all(model)):
            assert text == want[min(done[r] * WIDTH, len(kinds))], (t, r)


def test_apply_chunks_matches_apply_ops_bit_for_bit():
    trace = small_trace(11)
    a = BatchedList.from_trace(*trace, n_replicas=6, chunk_width=WIDTH)
    b = BatchedList.from_trace(*trace, n_replicas=6, chunk_width=WIDTH)
    rng = np.random.default_rng(5)
    for _ in range(12):
        ids = rng.integers(-1, a.n_chunks, a.n_replicas)
        a.apply_chunks(ids)
        ops = np.where(ids[:, None] >= 0, b.chunk_ops[np.maximum(ids, 0)], -1)
        b.apply_ops(ops)
        np.testing.assert_array_equal(np.asarray(a.vals), np.asarray(b.vals))
        np.testing.assert_array_equal(np.asarray(a.alive), np.asarray(b.alive))


def test_placed_model_applies_as_the_unplaced_one():
    from crdt_tpu.parallel import make_mesh

    trace = small_trace(12)
    plain = BatchedList.from_trace(*trace, n_replicas=4, chunk_width=WIDTH)
    placed = BatchedList(4, chunk_width=WIDTH)
    placed.place(make_mesh(2, 4))
    placed.extend_trace(*trace)
    rng = np.random.default_rng(6)
    for _ in range(6):
        ids = rng.integers(-1, plain.n_chunks, 4)
        plain.apply_chunks(ids)
        placed.apply_chunks(ids)
    assert read_all(placed) == read_all(plain)
    assert placed.vals.sharding == placed._state_sharding


def test_index_replay_matches_the_pure_list():
    """The reference's index replay and the repository's oracle
    (``crdt_tpu.pure.list.List``, identifiers and all) read the same text
    after every prefix of a trace."""
    from crdt_tpu.pure.list import List

    kinds, positions, values, actors = small_trace(13, n_ops=320, n_inserts=224)
    want = ref.texts_at(kinds, positions, values, range(len(kinds) + 1))
    oracle = List()
    assert oracle.read() == want[0]
    for i in range(len(kinds)):
        if kinds[i] == INSERT:
            op = oracle.insert_index(int(positions[i]), int(values[i]), int(actors[i]))
        else:
            op = oracle.delete_index(int(positions[i]), int(actors[i]))
        oracle.apply(op)
        assert oracle.read() == want[i + 1], i


@pytest.mark.parametrize("fn", [_list_apply_chunks, _scatter_ops])
def test_compiled_apply_aliases_its_state(fn):
    r, rows, c = 4, 8, 8
    state = (jnp.zeros((r, rows, LANES), jnp.int32), jnp.zeros((r, rows, LANES), bool))
    if fn is _list_apply_chunks:
        rest = (jnp.zeros((3, c), jnp.int32), jnp.zeros((3, c), jnp.uint8),
                jnp.zeros((3, c), jnp.int32), jnp.zeros(r, jnp.int32))
    else:
        rest = (jnp.zeros((r, c), jnp.int32), jnp.zeros((r, c), jnp.uint8),
                jnp.zeros((r, c), jnp.int32))
    text = _donated(fn).lower(*state, *rest).compile().as_text()
    assert "input_output_alias" in text
    # And at run time the donated buffers are consumed.
    _donated(fn)(*state, *rest)
    assert all(x.is_deleted() for x in state)


def test_apply_chunks_rejects_bad_ids():
    model = BatchedList.from_trace(*small_trace(14), n_replicas=3, chunk_width=WIDTH)
    for bad in ([0, 1], [0, 1, model.n_chunks], [0, -2, 1], [0.0, 1.0, 2.0]):
        with pytest.raises(ValueError):
            model.apply_chunks(np.asarray(bad))
    assert read_all(model) == [[], [], []]


def test_checkpoint_keeps_the_chunk_log(tmp_path):
    from crdt_tpu.checkpoint import load, save

    model = BatchedList.from_trace(*small_trace(15), n_replicas=3, chunk_width=WIDTH)
    model.apply_chunks(np.asarray([0, 0, -1]))
    save(tmp_path / "list.npz", model)
    back = load(tmp_path / "list.npz")
    assert back.chunk_width == WIDTH
    np.testing.assert_array_equal(back.chunk_ops, model.chunk_ops)
    for m in (model, back):
        m.apply_chunks(np.asarray([1, 1, 0]))
    assert read_all(back) == read_all(model)


def test_checkpoint_of_a_flat_state_restores():
    """A checkpoint written while the state was ``[R, N]`` (and the meta
    had no chunk width) restores into the row layout and applies on."""
    from crdt_tpu.checkpoint import _dump, _restore
    from crdt_tpu.models.list import CHUNK_WIDTH

    model = BatchedList.from_trace(*small_trace(18), n_replicas=3)
    model.apply_chunks(np.asarray([0, -1, 0]))
    meta, arrays = _dump(model)
    del meta["chunk_width"]
    n = len(model.slots)
    for k in ("vals", "alive"):
        arrays[k] = arrays[k].reshape(3, -1)[:, :n]
    back = _restore(meta, arrays)
    assert back.chunk_width == CHUNK_WIDTH
    assert back.vals.shape == model.vals.shape
    assert read_all(back) == read_all(model)
    np.testing.assert_array_equal(np.asarray(back.alive), np.asarray(model.alive))
    back.apply_chunks(np.asarray([-1, 0, -1]))
    assert read_all(back) == [model.read(0)] * 3


def test_generator_meets_the_published_totals():
    kw = dict(n_ops=259778, n_inserts=182315, jump_p=0.01, delete_run_mean=3.0, sites=8,
              run=128)
    kinds, positions, values, actors = edit_trace.generate(2900000001, **kw)
    assert len(kinds) == 259778
    assert (kinds == INSERT).sum() == 182315 and (kinds == DELETE).sum() == 77463
    # Every position addresses the document as it stands before the op.
    before = np.concatenate([[0], np.cumsum(np.where(kinds == INSERT, 1, -1))[:-1]])
    assert before[-1] + (1 if kinds[-1] == INSERT else -1) == 104852
    assert (positions >= 0).all()
    assert (positions[kinds == INSERT] <= before[kinds == INSERT]).all()
    assert (positions[kinds == DELETE] < before[kinds == DELETE]).all()
    assert ((values >= 32) & (values <= 126)).all()
    np.testing.assert_array_equal(actors[:256], np.repeat([0, 1], 128))
    again = edit_trace.generate(2900000001, **kw)
    for x, y in zip((kinds, positions, values, actors), again):
        np.testing.assert_array_equal(x, y)
    other = edit_trace.generate(2900000002, **kw)
    assert not np.array_equal(positions, other[1])


def test_control_is_caught():
    """The replay with the deletes dropped reads differently from the
    program, which reads as the true replay."""
    trace = small_trace(16)
    model = BatchedList.from_trace(*trace, n_replicas=2, chunk_width=WIDTH)
    for k in range(model.n_chunks):
        model.apply_chunks(np.asarray([k, k]))
    n = len(trace[0])
    true = ref.texts_at(*trace[:3], [n])[n]
    control = ref.texts_at(*trace[:3], [n], drop_deletes=True)[n]
    assert ref.words_off(model.read(0), true) == 0
    assert ref.words_off(model.read(0), control) > 0


def _first_duplicate(ops):
    """The per-row loop the vectorised check replaced."""
    for r in range(ops.shape[0]):
        live = ops[r][ops[r] >= 0]
        uniq, counts = np.unique(live, return_counts=True)
        if (counts > 1).any():
            return r, int(uniq[counts > 1][0])
    return None


@pytest.mark.parametrize("seed", range(6))
def test_strict_duplicate_check_matches_the_row_loop(seed):
    trace = small_trace(17)
    model = BatchedList.from_trace(*trace, n_replicas=5, chunk_width=WIDTH)
    rng = np.random.default_rng(seed)
    ops = rng.choice(np.arange(-1, len(trace[0])), size=(5, 12))
    ops[rng.integers(0, 5), :2] = rng.integers(0, len(trace[0]))  # a duplicate
    ops[rng.integers(0, 5), 3:] = -1                               # pads repeat freely
    want = _first_duplicate(ops)
    with configured(strict=True):
        if want is None:
            model.apply_ops(ops)
            return
        with pytest.raises(DotRange) as err:
            model.apply_ops(ops)
    assert err.value.actor == f"replica {want[0]} trace op"
    assert err.value.counter == err.value.next_counter == want[1]
