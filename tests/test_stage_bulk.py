"""The live rung stages a batch's first-descendant cells in bulk (the
listener is handed ancestor hashes, `_build_batch` resolves a whole batch of
them at once, `incremental._pack_upd` writes the three update slices with
numpy) into the batch's one buffer (`incremental.BatchLayout`), and the
device is handed the bytes it was handed before.

The yardstick is kept here: `PlainStage.build_batch`, `plain_pack_upd`,
`plain_dep_levels` and `plain_batches_from_grid` are the per-cell loops as
they stood (a Python tuple a cell, read back, and copied by three scalar
stores), so that a later change to the staging is still compared with the
plain loops and not with itself. Every field of every batch, padding
included, is compared byte for byte, batch by batch, on the same inserts:
the plain `Batch` packed (`BatchLayout.pack`) against the buffer the
engine wrote.
"""

import random

import numpy as np
import pytest

from babble_tpu.hashgraph import Hashgraph, InmemStore
from babble_tpu.hashgraph.hashgraph import middle_bit
from babble_tpu.tpu import live as live_mod
from babble_tpu.tpu import synthetic_grid
from babble_tpu.tpu.grid import MAX_INT32, GridUnsupported
from babble_tpu.tpu.incremental import (
    FEW_CELLS, L_MAX, Batch, BatchLayout, batches_from_grid,
)
from babble_tpu.tpu.live import LiveDeviceEngine, derive_fd_updates
from benchmark.entries import replay

from test_insert_once import PlainInmemStore, PlainInsert
from test_withheld_stream import drive, withheld_stream


# ---------------------------------------------------------------------------
# the plain reference: the per-cell loops as they were
# ---------------------------------------------------------------------------


def plain_pack_upd(upd, upd_cap, e_cap):
    urow = np.full(upd_cap, e_cap, dtype=np.int32)
    ucol = np.zeros(upd_cap, dtype=np.int32)
    uval = np.zeros(upd_cap, dtype=np.int32)
    for k, (r, c, v) in enumerate(upd):
        urow[k], ucol[k], uval[k] = r, c, v
    return urow, ucol, uval


def plain_dep_levels(sp_pos, op_pos):
    b = len(sp_pos)
    lvl = np.zeros(b, dtype=np.int64)
    for k in range(b):
        d = 0
        for parent in (int(sp_pos[k]), int(op_pos[k])):
            if parent >= 0:
                d = max(d, lvl[parent] + 1)
        lvl[k] = d
    return lvl


class PlainStage:
    """`LiveDeviceEngine._build_batch` with a tuple a cell, on a copy of
    the engine's row map as it stands, so that the engine's own build of
    the same chunk starts from the same rows."""

    def __init__(self, eng):
        self.eng = eng
        self.row_of = dict(eng.row_of)
        self.hashes = list(eng.hashes)
        self.dropped = 0

    def build_batch(self, chunk):
        eng = self.eng
        n, b_cap = eng.n, eng.batch_cap
        b = len(chunk)
        rows = []
        creator = np.zeros(b_cap, dtype=np.int32)
        index = np.full(b_cap, MAX_INT32, dtype=np.int32)
        sp_row = np.full(b_cap, -1, dtype=np.int32)
        op_row = np.full(b_cap, -1, dtype=np.int32)
        la_rows = np.full((b_cap, n), -1, dtype=np.int32)
        coin = np.zeros(b_cap, dtype=bool)
        fixed_round = np.full(b_cap, -1, dtype=np.int32)
        parent_lamport = np.full((2, b_cap), -1, dtype=np.int32)
        upd = []

        for k, (ev, fd_writes) in enumerate(chunk):
            row = len(self.hashes)
            h = ev.hex()
            self.row_of[h] = row
            self.hashes.append(h)
            rows.append(row)

            creator[k] = eng.hg.peer_position(ev.creator())
            index[k] = ev.index()
            sp = self.row_of.get(ev.self_parent(), -1)
            op = self.row_of.get(ev.other_parent(), -1)
            if sp < 0 and ev.index() != 0:
                parent_lamport[0, k] = eng._pruned_lamport(ev.self_parent())
            if op < 0 and ev.other_parent() != "":
                parent_lamport[1, k] = eng._pruned_lamport(ev.other_parent())
            if sp < 0 and ev.other_parent() == "":
                if eng.round_base > 0:
                    raise GridUnsupported("root attachment after rebase")
                fixed_round[k] = 0
            sp_row[k] = sp
            op_row[k] = op
            la_rows[k] = [c[0] for c in ev.last_ancestors]
            coin[k] = middle_bit(h)
            for ah, pos, val in fd_writes:
                arow = self.row_of.get(ah)
                if arow is None:
                    self.dropped += 1
                    continue
                upd.append((arow, pos, val))

        if len(upd) > eng.upd_cap:
            raise GridUnsupported("fd update burst exceeds device staging")

        base_row = rows[0]
        lvl = np.zeros(b, dtype=np.int64)
        for k in range(b):
            d = 0
            for parent in (int(sp_row[k]), int(op_row[k])):
                if parent >= base_row:
                    d = max(d, lvl[parent - base_row] + 1)
            lvl[k] = d
        levels = np.full((L_MAX, b_cap), -1, dtype=np.int32)
        slot = np.zeros(L_MAX, dtype=np.int64)
        for k in range(b):
            levels[lvl[k], slot[lvl[k]]] = k
            slot[lvl[k]] += 1

        urow, ucol, uval = plain_pack_upd(upd, eng.upd_cap, eng.e_cap)
        brows = np.full(b_cap, -1, dtype=np.int32)
        brows[:b] = rows
        return (
            Batch(
                rows=brows, creator=creator, index=index,
                sp_row=sp_row, op_row=op_row, la_rows=la_rows, coin=coin,
                fixed_round=fixed_round,
                upd_row=urow, upd_col=ucol, upd_val=uval, levels=levels,
                sp_lamport=parent_lamport[0], op_lamport=parent_lamport[1],
            ),
            rows,
        )


def plain_batches_from_grid(grid, stream, batch_size, upd_cap, e_cap):
    """`stream`: per event, its (row, col, value) triples."""

    def pad1(a, pad, fill, dtype=np.int32):
        a = np.asarray(a, dtype=dtype)
        return np.concatenate([a, np.full(pad, fill, dtype=dtype)])

    spans = [(s, min(s + batch_size, grid.e))
             for s in range(0, grid.e, batch_size)]
    out = []
    while spans:
        start, end = spans.pop(0)
        rows = np.arange(start, end)
        b = len(rows)
        pad = batch_size - b
        sp = grid.self_parent[rows]
        op = grid.other_parent[rows]
        sp_loc = np.where((sp >= start) & (sp < end), sp - start, -1)
        op_loc = np.where((op >= start) & (op < end), op - start, -1)
        lvl = plain_dep_levels(sp_loc, op_loc)
        l_b = int(lvl.max(initial=-1)) + 1 if b else 0
        upd = [t for r in rows for t in stream[r]]
        if l_b > L_MAX or (len(upd) > upd_cap and b > 1):
            mid = (start + end) // 2
            spans[:0] = [(start, mid), (mid, end)]
            continue
        levels_full = np.full((L_MAX, batch_size), -1, dtype=np.int32)
        slot = np.zeros(max(l_b, 1), dtype=np.int64)
        for k in range(b):
            levels_full[lvl[k], slot[lvl[k]]] = k
            slot[lvl[k]] += 1
        if len(upd) > upd_cap:
            raise ValueError(f"fd update burst {len(upd)} exceeds cap {upd_cap}")
        urow, ucol, uval = plain_pack_upd(upd, upd_cap, e_cap)
        no_row = np.full(batch_size, -1, dtype=np.int32)
        out.append(Batch(
            rows=pad1(rows, pad, -1),
            creator=pad1(grid.creator[rows], pad, 0),
            index=pad1(grid.index[rows], pad, MAX_INT32),
            sp_row=pad1(sp, pad, -1),
            op_row=pad1(op, pad, -1),
            la_rows=np.concatenate(
                [grid.last_ancestors[rows],
                 np.full((pad, grid.n), -1, dtype=np.int32)]),
            coin=pad1(grid.coin_bit[rows], pad, False, dtype=bool),
            fixed_round=pad1(grid.fixed_round[rows], pad, -1),
            upd_row=urow, upd_col=ucol, upd_val=uval,
            levels=levels_full,
            sp_lamport=no_row, op_lamport=no_row,
        ))
    return out


def plain_derive_fd_updates(grid):
    rows_by = np.full(
        (grid.n, int(grid.index.max(initial=0)) + 1), -1, dtype=np.int32)
    if grid.e:
        rows_by[grid.creator, grid.index] = np.arange(grid.e, dtype=np.int32)
    stream = [[] for _ in range(grid.e)]
    rows, cols = np.nonzero(grid.first_descendants != MAX_INT32)
    vals = grid.first_descendants[rows, cols]
    for row, c, v in zip(rows.tolist(), cols.tolist(), vals.tolist()):
        updater = int(rows_by[c, v])
        if updater != row:
            stream[updater].append((int(row), int(c), int(v)))
    return stream


# ---------------------------------------------------------------------------
# both builds of one chunk
# ---------------------------------------------------------------------------


def assert_same_batch(got, want, layout, where):
    """`got`, a batch's buffer, holds the plain `Batch` `want`: field by
    field in the field's shape, and byte for byte as a whole."""
    fields = layout.fields(got)
    assert got.dtype == np.int32 and got.shape == (layout.width,), where
    for f in Batch._fields:
        a, b = getattr(fields, f), np.asarray(getattr(want, f))
        assert a.shape == b.shape, (where, f)
        assert np.array_equal(a, b), (where, f)
    assert got.tobytes() == layout.pack(want).tobytes(), where


def attempt(build):
    try:
        return build()
    except GridUnsupported as e:
        return e


ENGINE_BUILD = LiveDeviceEngine._build_batch


class Compared:
    """What the chunks of one run came to."""

    def __init__(self):
        self.batches = 0
        self.handed = 0  # cells the chunks carried
        self.dropped = 0  # of them, cells of ancestors that are no row
        self.staged = 0  # what the engine says it staged
        self.few = 0  # batches the packer took cell by cell (FEW_CELLS)
        self.bulk = 0  # and in bulk
        self.dropped_by = set()  # which of the two dropped a pruned cell
        self.events = []  # events a chunk
        self.parent_lamports = 0  # sp_lamport / op_lamport handed over
        self.refused = []


def build_both(eng, chunk, seen):
    """`chunk` through the plain loops and through the engine's own
    `_build_batch`, from the same rows: the same Batch, the same rows, the
    same row map after it, or the same refusal. Returns the engine's."""
    position = eng.hg.peer_position
    triples = [(ev, [(ah, position(ev.creator()), ev.index()) for ah in cells])
               for ev, cells in chunk]
    plain = PlainStage(eng)
    want = attempt(lambda: plain.build_batch(triples))
    staged_before = eng.cells_staged
    got = attempt(lambda: ENGINE_BUILD(eng, chunk))
    where = f"batch {seen.batches}"
    seen.batches += 1
    assert eng.row_of == plain.row_of and eng.hashes == plain.hashes, where
    if isinstance(want, GridUnsupported):
        assert type(got) is GridUnsupported and str(got) == str(want), where
        seen.refused.append(str(got))
        return got
    buf, rows = got
    batch = eng.batch_layout.fields(buf)
    staged = eng.cells_staged - staged_before
    assert_same_batch(buf, want[0], eng.batch_layout, where)
    assert rows == want[1], where
    handed = sum(len(cells) for _, cells in chunk)
    assert staged == handed - plain.dropped, where
    assert staged == int((batch.upd_row != eng.e_cap).sum()), where
    seen.handed += handed
    seen.few += handed <= FEW_CELLS
    seen.bulk += handed > FEW_CELLS
    if plain.dropped:
        seen.dropped_by.add("few" if handed <= FEW_CELLS else "bulk")
    seen.dropped += plain.dropped
    seen.staged += staged
    seen.events.append(len(chunk))
    seen.parent_lamports += int((batch.sp_lamport >= 0).sum()
                                + (batch.op_lamport >= 0).sum())
    return got


def stage_only(stream, events, sync_sizes, **caps):
    """`events` of the stream inserted into a fresh hashgraph with an engine
    attached, drained sync by sync through the engine's own chunking
    (`_cut`, `_build_batch`) with nothing launched: staging alone, at any
    width, on this CPU."""
    hg = Hashgraph(stream.peers, InmemStore(stream.peers, 50000))
    eng = LiveDeviceEngine(hg, **{"e_cap": 4096, **caps})
    seen = Compared()
    done = 0
    for size in sync_sizes:
        if done >= events:
            break
        for signed in stream.signed[done : min(done + size, events)]:
            hg.insert_event(stream.copy(signed), True)
        done += size
        drained, eng.pending = eng.pending, []
        pos = 0
        while pos < len(drained):
            chunk = eng._cut(drained[pos : pos + eng.batch_cap])
            pos += len(chunk)
            got = build_both(eng, chunk, seen)
            if isinstance(got, GridUnsupported):
                return eng, seen
    return eng, seen


def compared_in_core(monkeypatch):
    """Every `_build_batch` of every engine from here on goes through both
    builds."""
    seen = Compared()

    def both(eng, chunk):
        got = build_both(eng, chunk, seen)
        if isinstance(got, GridUnsupported):
            raise got
        return got

    monkeypatch.setattr(LiveDeviceEngine, "_build_batch", both)
    return seen


@pytest.fixture(scope="module")
def stream64():
    return replay.Stream(64, 3000, 5, 1.1, 1)


# ---------------------------------------------------------------------------
# (a) staging alone: the widths and batch sizes the cells run
# ---------------------------------------------------------------------------


def test_v64_batches_of_32_are_byte_equal(stream64):
    """64 validators, 500-event syncs in 32-event batches: ~64 cells an
    event, ~2,000 a batch."""
    eng, seen = stage_only(stream64, 3000, [500] * 6, batch_cap=32)
    assert seen.refused == [] and len(eng.hashes) == 3000
    assert seen.batches >= 3000 // 32 and max(seen.events) == 32
    assert seen.dropped == 0 and seen.staged == seen.handed > 3000 * 40
    assert seen.bulk > 80  # whole batches are far over FEW_CELLS
    assert eng.update_cuts == 0  # ~2,000 cells a batch: none passes 8,192


@pytest.fixture(scope="module")
def stream128():
    return replay.Stream(128, 6048, 5, 1.1, 1)


@pytest.mark.parametrize("cap", [8192, 4096])
def test_v128_batches_of_32_are_byte_equal(stream128, cap):
    """128 validators, the cells' lead-in (32-event syncs) and then
    500-event syncs in 32-event batches: ~100 cells an event on a young
    honest DAG, ~3,200 a batch. Under the engine's staging of 8,192 no
    batch of this stream is cut; at half of it `_cut` ends the batches that
    would pass it, counts them, and what is staged fits."""
    events = 6048
    eng, seen = stage_only(stream128, events, [32] * 64 + [500] * 8,
                           batch_cap=32, e_cap=8192, upd_cap=cap)
    assert seen.refused == [] and len(eng.hashes) == events
    assert max(seen.events) == 32
    assert seen.dropped == 0 and seen.staged == seen.handed > events * 80
    if cap == 8192:
        assert eng.update_cuts == 0
    else:
        assert 10 < eng.update_cuts <= sum(size < 32 for size in seen.events)


@pytest.mark.parametrize("n", [64, 128])
def test_update_arrays_are_the_plain_walks(n, stream64, stream128):
    """The insert writes its cells as ranges of one table; the plain walk
    (`tests/test_insert_once.py PlainInsert`: a look-up and a list cell an
    ancestor) on the same stream hands its listener the same ancestors in
    the same order, event for event, and the batches staged from either are
    byte-equal in every field, the three update arrays among them."""
    stream = stream64 if n == 64 else stream128
    peers, events = stream.peers, 3000
    hg = Hashgraph(peers, InmemStore(peers, 50000))
    eng = LiveDeviceEngine(hg, e_cap=4096, batch_cap=32)
    plain = PlainInsert(peers, PlainInmemStore(peers, 50000))
    walked = []
    plain.insert_listener = lambda ev, writes: walked.append(list(writes))
    done = batches = 0
    for lo in range(0, events, 500):
        for signed in stream.signed[lo : lo + 500]:
            hg.insert_event(stream.copy(signed), True)
            plain.insert_event(stream.copy(signed), True)
        drained, eng.pending = eng.pending, []
        pos = 0
        while pos < len(drained):
            chunk = eng._cut(drained[pos : pos + eng.batch_cap])
            pos += len(chunk)
            triples = list(zip((ev for ev, _ in chunk),
                               walked[done : done + len(chunk)]))
            for (ev, cells), (_, writes) in zip(chunk, triples):
                assert cells == [ah for ah, _, _ in writes]
                assert {(hg.peer_position(ev.creator()), ev.index())} >= {
                    (pos_, val) for _, pos_, val in writes}
            done += len(chunk)
            want, _ = PlainStage(eng).build_batch(triples)
            got, _ = ENGINE_BUILD(eng, chunk)
            assert_same_batch(got, want, eng.batch_layout, f"batch {batches}")
            batches += 1
    assert done == events and batches >= events // 32
    assert eng.cells_staged == sum(map(len, walked)) > events * 40


@pytest.mark.parametrize("n", [8, 4])
def test_served_shape_is_byte_equal(n):
    """Syncs of 1 to 5 events, each one batch: the shape a served node
    stages, where a batch carries a handful of cells or none."""
    stream = replay.Stream(n, 900, 3, 1.1, 1)
    rng = random.Random(n)
    sizes = [rng.randint(1, 5) for _ in range(900)]
    eng, seen = stage_only(stream, 900, sizes)
    assert seen.refused == [] and len(eng.hashes) == 900
    assert set(seen.events) == {1, 2, 3, 4, 5}
    assert seen.dropped == 0 and seen.staged == seen.handed > 900
    # 4 validators stay under FEW_CELLS; 8 cross it now and then
    assert seen.few > 200 and (seen.bulk == 0 if n == 4 else True)


def test_a_batch_cut_at_the_update_cap_is_byte_equal(stream64):
    """A staging of 1,024 cells: `_cut` ends a batch where the next event
    would pass it (64 validators write ~64 cells an event), and what is
    staged fits."""
    eng, seen = stage_only(stream64, 1500, [500] * 3, batch_cap=32,
                           upd_cap=1024)
    assert seen.refused == [] and len(eng.hashes) == 1500
    # the first batches, of chains a few events long, are whole; most are cut
    assert seen.batches > 1500 // 32 + 20 and min(seen.events) < 20
    assert seen.staged == seen.handed
    # every batch but a sync's last was ended by the cap, and counted
    assert eng.update_cuts >= seen.batches - 3 - 1500 // 32


def test_one_event_over_the_update_cap_is_refused_alike(stream64):
    """An event whose own cells pass the staging is left to
    `_build_batch`, which refuses it as the plain loop did."""
    _, seen = stage_only(stream64, 1500, [500] * 3, batch_cap=32, upd_cap=32)
    assert seen.refused == ["fd update burst exceeds device staging"]
    assert seen.events and seen.events[-1] >= 1


# ---------------------------------------------------------------------------
# (b) through a Core: rebases, pruned ancestors, parents' lamport stamps,
# and the total the stage hands the tracer
# ---------------------------------------------------------------------------


def count_handed(monkeypatch):
    """The cells every engine's listener is handed."""
    handed = [0]
    on_insert = LiveDeviceEngine._on_insert

    def counting(eng, event, cells):
        handed[0] += len(cells)
        on_insert(eng, event, cells)

    monkeypatch.setattr(LiveDeviceEngine, "_on_insert", counting)
    return handed


def withheld_run(monkeypatch, batch_rows=32):
    """A withheld-and-revealed stream at 8 validators over a round axis of
    16: the axis is rebased every few rounds, and a chain revealed after a
    rebase names ancestors and parents the rebase pruned."""
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "r_cap", 16)
    stream, _ = withheld_stream(monkeypatch, 8, 3200, 1, "12-48")
    return drive(stream, "tpu", 100, {"dispatch_batch_rows": batch_rows})[0]


def withheld_run_small_batches(monkeypatch):
    """The same in batches of 4 events, about 32 cells: under FEW_CELLS."""
    return withheld_run(monkeypatch, batch_rows=4)


def plain_run(monkeypatch):
    """A plain stream at 4 validators in syncs of 4 events."""
    stream = replay.Stream(4, 600, 2, 1.1, 1)
    return drive(stream, "tpu", 4, {})[0]


@pytest.mark.parametrize(
    "run", [withheld_run, withheld_run_small_batches, plain_run],
    ids=["withheld-rebased", "withheld-rebased-rows4", "plain-v4"])
def test_stage_cells_total_is_what_was_handed_less_the_pruned(monkeypatch, run):
    monkeypatch.setitem(live_mod.ENGINE_DEFAULTS, "async_fetch", False)
    seen = compared_in_core(monkeypatch)
    handed = count_handed(monkeypatch)
    core = run(monkeypatch)
    assert core.ladder_rung() == "live" and core.live_demotions == 0
    assert seen.refused == [] and seen.batches > 20
    totals = core.hg.obs.tracer.totals()
    assert handed[0] == seen.handed > 0
    assert totals["stage.cells"][0] == seen.staged == handed[0] - seen.dropped
    # once a dispatch, like the launch totals beside it
    spans = core.hg.obs.tracer.spans()
    staged = [s.attrs["fd_updates"] for s in spans if s.name == "live.stage"]
    assert sum(staged) <= seen.handed  # the ring may have wrapped
    if run is not plain_run:
        eng = core.device_engine
        assert eng.rebases > 2 and eng.round_base > 0
        assert seen.dropped > 0 and seen.parent_lamports > 0
        # each side of the packer's selection drops the pruned cells itself
        assert ("bulk" if run is withheld_run else "few") in seen.dropped_by
    else:
        assert seen.dropped == 0 and max(seen.events) <= 4


# ---------------------------------------------------------------------------
# (c) the attach: batches from a grid, one packer
# ---------------------------------------------------------------------------


def triples_of(grid, stream):
    return [[(int(r), int(grid.creator[i]), int(grid.index[i])) for r in cells]
            for i, cells in enumerate(stream)]


@pytest.mark.parametrize("n,e,byz,rows,cap", [
    (8, 700, 0.0, 32, 8192),
    (8, 700, 1 / 3, 32, 8192),
    (8, 700, 1 / 3, 32, 100),  # halved at the update cap
    (16, 500, 0.0, 5, 8192),
    (64, 400, 0.0, 32, 8192),
], ids=["v8", "v8-withheld", "v8-cap100", "v16-rows5", "v64"])
def test_batches_from_grid_are_byte_equal(n, e, byz, rows, cap):
    grid = synthetic_grid(n, e, seed=3, zipf_a=1.1, record_fd_updates=True,
                          byzantine_frac=byz)
    # the stream a grid records and the one the attach derives from its
    # first descendants say the same cells (a cell is written once)
    derived = derive_fd_updates(grid)
    assert [sorted(c) for c in derived] == [sorted(c) for c in
                                            grid.fd_update_stream]
    assert triples_of(grid, derived) == plain_derive_fd_updates(grid)
    got = batches_from_grid(grid, rows, cap, 1024)
    want = plain_batches_from_grid(
        grid, triples_of(grid, grid.fd_update_stream), rows, cap, 1024)
    assert len(got) == len(want) >= e // rows
    if cap == 100:
        assert len(got) > -(-e // rows)
    layout = BatchLayout(rows, n, cap)
    for k, (a, b) in enumerate(zip(got, want)):
        assert_same_batch(a, b, layout, f"batch {k}")


def test_an_event_over_the_cap_in_a_grid_is_refused_alike():
    grid = synthetic_grid(16, 300, seed=3, zipf_a=1.1, record_fd_updates=True)
    stream = triples_of(grid, grid.fd_update_stream)
    with pytest.raises(ValueError, match="fd update burst"):
        plain_batches_from_grid(grid, stream, 32, 4, 1024)
    # the one check of the cap is the packer's, for an attach as for a sync
    with pytest.raises(GridUnsupported, match="fd update burst"):
        batches_from_grid(grid, 32, 4, 1024)


# ---------------------------------------------------------------------------
# (d) which capacities follow the validator count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,e_win", [(4, 8192), (16, 8192), (64, 16384),
                                     (128, 32768)])
def test_capacities_by_width(n, e_win):
    """The received window follows the validator count (8,192 rows per 32
    validators); the staging, the event axis and the round axis are flat,
    so every width's programs keep the shapes they had. At 128 `_cut`
    serves the batches that pass the staging (above, and
    tests/test_withheld_stream.py)."""
    stream = replay.Stream(n, n, 1, 1.1, 1)
    hg = Hashgraph(stream.peers, InmemStore(stream.peers, 100))
    eng = LiveDeviceEngine(hg)
    d = live_mod.ENGINE_DEFAULTS
    assert (eng.n, eng.e_win) == (n, e_win)
    assert (eng.upd_cap, eng.e_cap, eng.r_cap, eng.r_win) == (
        d["upd_cap"], d["e_cap"], d["r_cap"], d["r_win"]) == (8192, 65536, 64, 64)
    assert eng.batch_layout.fields(eng.empty_batch).upd_row.shape == (8192,)
