"""Consensus-core tests on hand-built DAGs
(reference: src/hashgraph/hashgraph_test.go).

Scenario tables (ancestry, rounds, timestamps, fame, consensus order) are
transcribed from the reference so the rebuilt engine is checked against the
same expectations.
"""

import pytest

from babble_tpu.common import StoreErr
from babble_tpu.hashgraph import (
    Hashgraph,
    InmemStore,
    RoundInfo,
    SQLiteStore,
    Trilean,
)
from dsl import (
    CACHE_SIZE,
    get_name,
    init_consensus_hashgraph,
    init_round_hashgraph,
    init_simple_hashgraph,
)

MAX_INT32 = 2**31 - 1


def sqlite_factory(tmp_path):
    def factory(participants):
        return SQLiteStore(participants, CACHE_SIZE, str(tmp_path / "store.db"))

    return factory


# ---------------------------------------------------------------------------
# ancestry predicates (reference: TestAncestor :204, TestSelfAncestor :251,
# TestSee :283, TestLamportTimestamp :308)
# ---------------------------------------------------------------------------


class TestSimpleDag:
    @pytest.fixture(autouse=True)
    def setup(self):
        self.h, self.index, _ = init_simple_hashgraph()

    def check(self, fn, table):
        for descendant, ancestor, val in table:
            assert fn(self.index[descendant], self.index[ancestor]) == val, (
                f"{fn.__name__}({descendant}, {ancestor}) should be {val}"
            )

    def test_ancestor(self):
        self.check(
            self.h.ancestor,
            [
                # first generation
                ("e01", "e0", True),
                ("e01", "e1", True),
                ("s00", "e01", True),
                ("s20", "e2", True),
                ("e20", "s00", True),
                ("e20", "s20", True),
                ("e12", "e20", True),
                ("e12", "s10", True),
                # second generation
                ("s00", "e0", True),
                ("s00", "e1", True),
                ("e20", "e01", True),
                ("e20", "e2", True),
                ("e12", "e1", True),
                ("e12", "s20", True),
                # third generation
                ("e20", "e0", True),
                ("e20", "e1", True),
                ("e20", "e2", True),
                ("e12", "e01", True),
                ("e12", "e0", True),
                ("e12", "e1", True),
                ("e12", "e2", True),
                # false positives
                ("e01", "e2", False),
                ("s00", "e2", False),
            ],
        )

    def test_ancestor_unknown_raises(self):
        with pytest.raises((StoreErr, KeyError)):
            self.h.ancestor(self.index["e0"], "")

    def test_self_ancestor(self):
        self.check(
            self.h.self_ancestor,
            [
                ("e01", "e0", True),
                ("s00", "e01", True),
                ("e01", "e1", False),
                ("e12", "e20", False),
                ("s20", "e1", False),
                ("e20", "e2", True),
                ("e12", "e1", True),
                ("e20", "e0", False),
                ("e12", "e2", False),
                ("e20", "e01", False),
            ],
        )

    def test_see(self):
        self.check(
            self.h.see,
            [
                ("e01", "e0", True),
                ("e01", "e1", True),
                ("e20", "e0", True),
                ("e20", "e01", True),
                ("e12", "e01", True),
                ("e12", "e0", True),
                ("e12", "e1", True),
                ("e12", "s20", True),
            ],
        )

    def test_lamport_timestamp(self):
        expected = {
            "e0": 0,
            "e1": 0,
            "e2": 0,
            "e01": 1,
            "s10": 1,
            "s20": 1,
            "s00": 2,
            "e20": 3,
            "e12": 4,
        }
        for name, ts in expected.items():
            assert self.h.lamport_timestamp(self.index[name]) == ts, name


# ---------------------------------------------------------------------------
# round hashgraph (reference: TestInsertEvent :436, TestStronglySee :611,
# TestWitness :645, TestRound :679, TestDivideRounds :743)
# ---------------------------------------------------------------------------


class TestRoundDag:
    @pytest.fixture(autouse=True)
    def setup(self):
        self.h, self.index, _ = init_round_hashgraph()

    def _set_round0_witnesses(self):
        ri = RoundInfo()
        for name in ("e0", "e1", "e2"):
            ri.add_event(self.index[name], True)
        self.h.store.set_round(0, ri)

    def test_insert_event_coordinates(self):
        h, index = self.h, self.index
        e0 = h.store.get_event(index["e0"])
        assert e0.body.self_parent_index == -1
        assert e0.body.other_parent_creator_id == -1
        assert e0.body.other_parent_index == -1
        assert e0.body.creator_id == h.participants.by_pub_key[e0.creator()].id

        assert e0.first_descendants == [
            (0, index["e0"]),
            (1, index["e10"]),
            (2, index["e21"]),
        ]
        assert e0.last_ancestors == [(0, index["e0"]), (-1, ""), (-1, "")]

        e21 = h.store.get_event(index["e21"])
        e10 = h.store.get_event(index["e10"])
        assert e21.body.self_parent_index == 1
        assert e21.body.other_parent_creator_id == h.participants.by_pub_key[e10.creator()].id
        assert e21.body.other_parent_index == 1

        assert e21.first_descendants == [
            (2, index["e02"]),
            (3, index["f1"]),
            (2, index["e21"]),
        ]
        assert e21.last_ancestors == [
            (0, index["e0"]),
            (1, index["e10"]),
            (2, index["e21"]),
        ]

        f1 = h.store.get_event(index["f1"])
        assert f1.body.self_parent_index == 2
        assert f1.body.other_parent_index == 2
        assert f1.first_descendants == [
            (MAX_INT32, ""),
            (3, index["f1"]),
            (MAX_INT32, ""),
        ]
        assert f1.last_ancestors == [
            (2, index["e02"]),
            (3, index["f1"]),
            (2, index["e21"]),
        ]

    def test_undetermined_events_and_pending_loaded(self):
        h, index = self.h, self.index
        expected = [
            index[n]
            for n in ["e0", "e1", "e2", "e10", "s20", "s00", "e21", "e02", "s10", "f1", "s11"]
        ]
        assert h.undetermined_events == expected
        # 3 events with index 0 + 1 event with transactions
        assert h.pending_loaded_events == 4

    def test_read_wire_info_roundtrip(self):
        h, index = self.h, self.index
        for name, evh in self.index.items():
            ev = h.store.get_event(evh)
            ev_from_wire = h.read_wire_info(ev.to_wire())
            assert ev.body.to_canonical() == ev_from_wire.body.to_canonical(), name
            assert ev.signature == ev_from_wire.signature, name
            assert ev_from_wire.verify(), name
            assert ev_from_wire.hex() == ev.hex(), name

    def test_strongly_see(self):
        table = [
            ("e21", "e0", True),
            ("e02", "e10", True),
            ("e02", "e0", True),
            ("e02", "e1", True),
            ("f1", "e21", True),
            ("f1", "e10", True),
            ("f1", "e0", True),
            ("f1", "e1", True),
            ("f1", "e2", True),
            ("s11", "e2", True),
            # false negatives
            ("e10", "e0", False),
            ("e21", "e1", False),
            ("e21", "e2", False),
            ("e02", "e2", False),
            ("s11", "e02", False),
        ]
        for x, y, val in table:
            assert self.h.strongly_see(self.index[x], self.index[y]) == val, (x, y)

    def test_witness(self):
        self._set_round0_witnesses()
        ri = RoundInfo()
        ri.add_event(self.index["f1"], True)
        self.h.store.set_round(1, ri)

        for name, val in [
            ("e0", True),
            ("e1", True),
            ("e2", True),
            ("f1", True),
            ("e10", False),
            ("e21", False),
            ("e02", False),
        ]:
            assert self.h.witness(self.index[name]) == val, name

    def test_round(self):
        self._set_round0_witnesses()
        for name, r in [
            ("e0", 0),
            ("e1", 0),
            ("e2", 0),
            ("s00", 0),
            ("e10", 0),
            ("s20", 0),
            ("e21", 0),
            ("e02", 0),
            ("s10", 0),
            ("f1", 1),
            ("s11", 1),
        ]:
            assert self.h.round(self.index[name]) == r, name

    def test_round_diff(self):
        self._set_round0_witnesses()
        assert self.h.round_diff(self.index["f1"], self.index["e02"]) == 1
        assert self.h.round_diff(self.index["e02"], self.index["f1"]) == -1
        assert self.h.round_diff(self.index["e02"], self.index["e21"]) == 0

    def test_divide_rounds(self):
        h, index = self.h, self.index
        h.divide_rounds()

        assert h.store.last_round() == 1
        round0 = h.store.get_round(0)
        assert sorted(round0.witnesses()) == sorted(
            [index["e0"], index["e1"], index["e2"]]
        )
        round1 = h.store.get_round(1)
        assert round1.witnesses() == [index["f1"]]

        assert [(pr.index, pr.decided) for pr in h.pending_rounds] == [
            (0, False),
            (1, False),
        ]

        expected = {
            "e0": (0, 0),
            "e1": (0, 0),
            "e2": (0, 0),
            "s00": (1, 0),
            "e10": (1, 0),
            "s20": (1, 0),
            "e21": (2, 0),
            "e02": (3, 0),
            "s10": (2, 0),
            "f1": (4, 1),
            "s11": (5, 1),
        }
        for name, (ts, r) in expected.items():
            ev = h.store.get_event(index[name])
            assert ev.round == r, name
            assert ev.lamport_timestamp == ts, name

    def test_create_root(self):
        h, index = self.h, self.index
        h.divide_rounds()
        participants = h.participants.to_peer_slice()

        from babble_tpu.hashgraph import Root, RootEvent, new_base_root

        expected = {
            "e0": new_base_root(participants[0].id),
            "e02": Root(
                next_round=0,
                self_parent=RootEvent(index["s00"], participants[0].id, 1, 1, 0),
                others={index["e02"]: RootEvent(index["e21"], participants[2].id, 2, 2, 0)},
            ),
            "s10": Root(
                next_round=0,
                self_parent=RootEvent(index["e10"], participants[1].id, 1, 1, 0),
                others={},
            ),
            "f1": Root(
                next_round=1,
                self_parent=RootEvent(index["s10"], participants[1].id, 2, 2, 0),
                others={index["f1"]: RootEvent(index["e02"], participants[0].id, 2, 3, 0)},
            ),
        }
        for name, exp in expected.items():
            ev = h.store.get_event(index[name])
            root = h._create_root(ev)
            assert root == exp, name


# ---------------------------------------------------------------------------
# consensus pipeline (reference: TestDivideRoundsBis :1208, TestDecideFame
# :1267, TestDecideRoundReceived :1346, TestProcessDecidedRounds :1419)
# ---------------------------------------------------------------------------


class TestConsensusPipeline:
    @pytest.fixture(autouse=True)
    def setup(self):
        self.h, self.index, _ = init_consensus_hashgraph()

    def test_divide_rounds_bis(self):
        h, index = self.h, self.index
        h.divide_rounds()
        expected = {
            "e0": (0, 0), "e1": (0, 0), "e2": (0, 0),
            "e10": (1, 0), "e21": (2, 0), "e21b": (3, 0), "e02": (4, 0),
            "f1": (5, 1), "f1b": (6, 1), "f0": (7, 1), "f2": (7, 1),
            "f10": (8, 1), "f0x": (8, 1), "f21": (9, 1), "f02": (10, 1),
            "f02b": (11, 1),
            "g1": (12, 2), "g0": (13, 2), "g2": (13, 2), "g10": (14, 2),
            "g21": (15, 2), "g02": (16, 2),
            "h1": (17, 3), "h0": (18, 3), "h2": (18, 3), "h10": (19, 3),
            "h21": (20, 3), "h02": (21, 3),
            "i1": (22, 4), "i0": (23, 4), "i2": (23, 4),
        }
        for name, (ts, r) in expected.items():
            ev = h.store.get_event(index[name])
            assert ev.round == r, f"{name} round"
            assert ev.lamport_timestamp == ts, f"{name} ts"

    def test_decide_fame(self):
        h, index = self.h, self.index
        h.divide_rounds()
        h.decide_fame()

        round0 = h.store.get_round(0)
        for name in ("e0", "e1", "e2"):
            assert round0.events[index[name]].famous == Trilean.TRUE, name
        round1 = h.store.get_round(1)
        for name in ("f0", "f1", "f2"):
            assert round1.events[index[name]].famous == Trilean.TRUE, name
        round2 = h.store.get_round(2)
        for name in ("g0", "g1", "g2"):
            assert round2.events[index[name]].famous == Trilean.TRUE, name

        assert [(pr.index, pr.decided) for pr in h.pending_rounds[:3]] == [
            (0, True),
            (1, True),
            (2, True),
        ]

    def test_decide_round_received(self):
        h, index = self.h, self.index
        h.divide_rounds()
        h.decide_fame()
        h.decide_round_received()

        for name, hash_ in index.items():
            e = h.store.get_event(hash_)
            if name.startswith("e"):
                assert e.round_received == 1, name
            elif name.startswith("f"):
                assert e.round_received == 2, name
            else:
                assert e.round_received is None, name

        assert len(h.store.get_round(0).consensus_events()) == 0
        assert len(h.store.get_round(1).consensus_events()) == 7
        assert len(h.store.get_round(2).consensus_events()) == 9

        expected_undetermined = [
            index[n]
            for n in [
                "g1", "g0", "g2", "g10", "g21", "g02",
                "h1", "h0", "h2", "h10", "h21", "h02",
                "i1", "i0", "i2",
            ]
        ]
        assert h.undetermined_events == expected_undetermined

    def test_process_decided_rounds(self):
        h, index = self.h, self.index
        committed = []
        h.commit_callback = committed.append
        h.divide_rounds()
        h.decide_fame()
        h.decide_round_received()
        h.process_decided_rounds()

        consensus_events = h.store.consensus_events()
        assert len(consensus_events) == 16
        assert h.pending_loaded_events == 2

        block0 = h.store.get_block(0)
        assert block0.index() == 0
        assert block0.round_received() == 1
        assert block0.transactions() == [b"e21"]
        frame1 = h.get_frame(block0.round_received())
        assert block0.frame_hash() == frame1.hash()

        block1 = h.store.get_block(1)
        assert block1.index() == 1
        assert block1.round_received() == 2
        assert len(block1.transactions()) == 2
        assert block1.transactions()[1] == b"f02b"
        frame2 = h.get_frame(block1.round_received())
        assert block1.frame_hash() == frame2.hash()

        assert [(pr.index, pr.decided) for pr in h.pending_rounds] == [
            (3, False),
            (4, False),
        ]
        assert h.anchor_block is None
        assert [b.index() for b in committed] == [0, 1]

    def test_settled_rounds_never_reminted(self):
        """Round-5 safety regression: a PendingRound at or below
        last_consensus_round must be dropped, never re-processed — even
        when the queue is out of round order. The live failure mode: a
        fast-synced joiner's section replay re-queues scrubbed rounds in
        section TOPOLOGICAL order; processing round N+1 first advances
        last_consensus_round past the settled anchor round N, after which
        the reference-shaped equality skip (`index == last_consensus_round`)
        no longer recognizes it and round N's frame is re-minted as a
        duplicate block at the next free index — shifting the joiner's
        whole chain one block against the cluster (observed in-suite:
        byte-divergent block 13, RR 12 duplicating block 11)."""
        from babble_tpu.hashgraph import PendingRound

        h = self.h
        committed = []
        h.commit_callback = committed.append
        h.run_consensus()
        assert [b.index() for b in committed] == [0, 1]
        last_block = h.store.last_block_index()
        lcr = h.last_consensus_round
        assert lcr == 2

        # stale re-queues of settled rounds, deliberately out of order
        # (the later round first, as section topological order produces)
        h.pending_rounds = [PendingRound(lcr, True), PendingRound(lcr - 1, True)]
        h.process_decided_rounds()

        assert h.store.last_block_index() == last_block, (
            "settled round was re-minted as a duplicate block"
        )
        assert [b.index() for b in committed] == [0, 1]
        assert h.pending_rounds == []
        assert h.last_consensus_round == lcr

    def test_known(self):
        h = self.h
        participants = h.participants.to_peer_slice()
        expected = {
            participants[0].id: 10,
            participants[1].id: 9,
            participants[2].id: 9,
        }
        assert h.store.known_events() == expected

    def test_full_pipeline_deterministic_order(self):
        """Two runs over the same DAG produce identical block bodies."""
        h1, index1, ordered = init_consensus_hashgraph()
        blocks1, blocks2 = [], []
        h1.commit_callback = blocks1.append
        h1.run_consensus()

        # replay the same signed events into a fresh hashgraph
        from dsl import create_hashgraph

        h2 = Hashgraph(h1.participants, InmemStore(h1.participants, CACHE_SIZE))
        h2.commit_callback = blocks2.append
        import json

        for ev in ordered:
            from babble_tpu.hashgraph import Event

            h2.insert_event(Event.from_json(json.loads(json.dumps(ev.to_json()))), True)
        h2.run_consensus()

        assert len(blocks1) == len(blocks2) > 0
        for b1, b2 in zip(blocks1, blocks2):
            assert b1.body.marshal() == b2.body.marshal()


# ---------------------------------------------------------------------------
# RoundInfo's decided state: a count kept where the flags change, equal to
# the scan over the round's events after every step
# ---------------------------------------------------------------------------


def scan_witnesses_decided(ri):
    """The scan `RoundInfo.witnesses_decided` was before it kept a count."""
    return all(
        not e.witness or e.famous != Trilean.UNDEFINED for e in ri.events.values()
    )


ROUND_INFO_SEQUENCES = {
    "witnesses_then_fame": [
        ("add", "w0", True), ("add", "x0", False), ("add", "w1", True),
        ("fame", "w0", True), ("add", "x1", False), ("fame", "w1", False),
    ],
    "known_event_added_again": [
        ("add", "w0", True), ("add", "w0", True), ("add", "w0", False),
        ("fame", "w0", True), ("add", "w0", True),
    ],
    "fame_set_twice": [
        ("add", "w0", True), ("fame", "w0", True), ("fame", "w0", False),
        ("fame", "w0", False),
    ],
    "late_witness_reopens_a_decided_round": [
        ("add", "w0", True), ("add", "w1", True), ("fame", "w0", True),
        ("fame", "w1", True), ("consensus", "x0"), ("add", "late", True),
        ("json",), ("fame", "late", False),
    ],
    "fame_on_an_unseen_witness": [
        ("fame", "u0", True), ("add", "u0", True), ("add", "w0", True),
        ("fame", "u1", False), ("fame", "w0", True),
    ],
    "consensus_event_first": [
        ("consensus", "c0"), ("add", "c0", True), ("fame", "c0", True),
        ("add", "w0", True), ("consensus", "w0"), ("fame", "w0", False),
    ],
    "fame_on_a_plain_event": [
        ("add", "x0", False), ("fame", "x0", True), ("add", "w0", True),
        ("fame", "x0", False), ("fame", "w0", True),
    ],
    "store_round_trips": [
        ("json",), ("add", "w0", True), ("json",), ("add", "w1", True),
        ("fame", "w0", True), ("json",), ("fame", "w1", True), ("json",),
        ("add", "late", True), ("json",), ("fame", "late", True),
    ],
}


@pytest.mark.parametrize("name", sorted(ROUND_INFO_SEQUENCES))
def test_round_info_count_equals_the_scan(name):
    ri = RoundInfo()
    assert ri.witnesses_decided() and scan_witnesses_decided(ri)
    seen = set()
    for step, op in enumerate(ROUND_INFO_SEQUENCES[name]):
        if op[0] == "add":
            ri.add_event(op[1], op[2])
        elif op[0] == "fame":
            ri.set_fame(op[1], op[2])
        elif op[0] == "consensus":
            ri.set_consensus_event(op[1])
        else:
            wire = ri.to_json()
            assert set(wire) == {"Events"}  # the count is derived, not stored
            assert all(set(e) == {"Consensus", "Witness", "Famous"}
                       for e in wire["Events"].values())
            back = RoundInfo.from_json(wire)
            assert back.to_json() == wire and back.events == ri.events
            ri = back
        assert ri.witnesses_decided() == scan_witnesses_decided(ri), (name, step, op)
        seen.add(ri.witnesses_decided())
    assert seen == {True, False}, name  # every sequence opens and closes


# ---------------------------------------------------------------------------
# an event's stamp is the memo of round() and lamport_timestamp()
# ---------------------------------------------------------------------------

EXPECTED = {  # name: (lamport timestamp, round), as test_divide_rounds_bis
    "e0": (0, 0), "e21b": (3, 0), "f1": (5, 1), "f0x": (8, 1),
    "g1": (12, 2), "g02": (16, 2), "h10": (19, 3), "i2": (23, 4),
}


def drop_memos(h):
    """What a device write-back leaves: stamps on the events, no memo."""
    h._round_cache.clear()
    h._timestamp_cache.clear()


class TestStampIsTheMemo:
    @pytest.fixture(autouse=True)
    def setup(self):
        self.h, self.index, self.ordered = init_consensus_hashgraph()

    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_unstamped_event_still_derives(self, name):
        h, x = self.h, self.index[name]
        h.divide_rounds()
        drop_memos(h)
        derived = h._derivations
        ev = h.store.get_event(x)
        ev.set_round(None)
        ev.set_lamport_timestamp(None)
        ts, r = EXPECTED[name]
        assert (h.lamport_timestamp(x), h.round(x)) == (ts, r)
        # e0 hangs on its root: a lookup, not a derivation; the others are
        # derived once, from their parents' stamps
        parents = sum(p in self.index.values() for p in ev.body.parents)
        assert h._derivations - derived == (name != "e0")
        assert h._stamp_reads == parents
        assert ev.round is None  # asking stamps nothing

    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_stamped_event_is_not_rederived(self, name):
        h, x = self.h, self.index[name]
        h.divide_rounds()
        drop_memos(h)
        derived = h._derivations
        ts, r = EXPECTED[name]
        assert (h.lamport_timestamp(x), h.round(x)) == (ts, r)
        assert (h._derivations, h._stamp_reads) == (derived, 1)
        assert h.round(x) == r and h._stamp_reads == 1  # remembered
        # the stamp, not a second derivation, is what was read
        drop_memos(h)
        ev = h.store.get_event(x)
        ev.set_round(r + 40)
        ev.set_lamport_timestamp(ts + 40)
        assert (h.lamport_timestamp(x), h.round(x)) == (ts + 40, r + 40)
        assert h._derivations == derived

    def test_frames_from_stamps_equal_frames_from_memos(self):
        from babble_tpu.hashgraph import Event

        bodies, totals = [], []
        for memos in (True, False):
            h = Hashgraph(self.h.participants,
                          InmemStore(self.h.participants, CACHE_SIZE))
            blocks = []
            h.commit_callback = lambda b, blocks=blocks: blocks.append(b.body.marshal())
            for ev in self.ordered:
                h.insert_event(Event.from_json(ev.to_json()), True)
            h.divide_rounds()
            h.decide_fame()
            h.decide_round_received()
            if not memos:
                drop_memos(h)
            h.process_decided_rounds()
            frames = [s for s in h.obs.tracer.spans() if s.name == "commit.frame"]
            assert [s.attrs["rounds_derived"] for s in frames] == [0, 0, 0]
            assert (h._stamp_reads, h._derivations) == (0, 0)  # handed over
            bodies.append(blocks)
            totals.append(h.obs.tracer.totals())
        assert bodies[0] == bodies[1] and len(bodies[0]) == 2
        assert "round.stamp" not in totals[0] and totals[1]["round.stamp"][0] > 0
        assert totals[0]["round.derive"] == totals[1]["round.derive"]

    def test_reset_leaves_neither_stamp_nor_memo(self):
        from babble_tpu.hashgraph import Frame

        h = self.h
        h.run_consensus()
        block = h.store.get_block(1)
        frame = h.get_frame(block.round_received())
        rounds = {ev.hex(): (h.lamport_timestamp(ev.hex()), h.round(ev.hex()))
                  for ev in frame.events}
        assert h._round_cache and h._timestamp_cache
        h.reset(block, Frame.from_json(frame.to_json()))
        assert h._round_cache == {} and h._timestamp_cache == {}
        stored = [h.store.get_event(ev.hex()) for ev in frame.events]
        assert stored and all(
            ev.round is None and ev.lamport_timestamp is None for ev in stored
        )
        # so the rounds after a reset are derived anew, from the frame's roots
        for x, known in rounds.items():
            assert (h.lamport_timestamp(x), h.round(x)) == known
        assert h._stamp_reads == 0


# ---------------------------------------------------------------------------
# persistence: same pipeline on the SQLite store
# ---------------------------------------------------------------------------


class TestSQLiteStorePipeline:
    def test_consensus_on_sqlite(self, tmp_path):
        h, index, _ = init_consensus_hashgraph(sqlite_factory(tmp_path))
        h.run_consensus()
        assert h.store.get_block(0).transactions() == [b"e21"]
        assert len(h.store.consensus_events()) == 16

    def test_bootstrap_replays_to_same_state(self, tmp_path):
        h, index, _ = init_consensus_hashgraph(sqlite_factory(tmp_path))
        h.run_consensus()
        block0 = h.store.get_block(0)
        block1 = h.store.get_block(1)
        participants = h.participants
        h.store.close()

        store2 = SQLiteStore(
            participants, CACHE_SIZE, str(tmp_path / "store.db"), existing_db=True
        )
        h2 = Hashgraph(participants, store2)
        assert store2.need_bootstrap()
        h2.bootstrap()
        assert h2.store.get_block(0).body.marshal() == block0.body.marshal()
        assert h2.store.get_block(1).body.marshal() == block1.body.marshal()
        assert h2.store.last_block_index() == h.store.last_block_index()
