"""The three readers of the chained UDP drain (``udp_chained_share``,
``udp_empty_recv_share``, ``udp_send_drops``) on a hand-made scrape pair:
one worker whose window holds a chain of three drains, an empty
``recvmmsg`` and a dropped answer in each lane; a program without the new
counters (the parent commit) reads ``None`` where the counter is absent,
not 0, and keeps the one reading it always had."""
import json
import os

import pytest

from test_loop_spans import NO_EVENTS, event_text, without
from test_spans import BENCH, metrics_text, parent_ctx, readers

THREE = ("udp_chained_share", "udp_empty_recv_share", "udp_send_drops")
CHAINED = "binder_udp_chained_drains_total"
DROPS = "binder_udp_send_drops_total"


def worker(recv_calls, batches, events, chained, drops):
    counters = {("binder_udp_batch_size_count", ""): batches,
                (CHAINED, ""): chained}
    counters.update({(DROPS, '{lane="%s"}' % lane): n
                     for lane, n in drops.items()})
    lanes = dict(NO_EVENTS, udp=(0.001 * events, [0, events, 0, 0, 0]))
    return {"metrics": metrics_text({"udp-recv": 0.0001 * recv_calls},
                                    {"udp-recv": recv_calls}, counters)
            + event_text(lanes), "status": {}}


NO_DROPS = {"native": 0, "python": 0, "balancer": 0}
ZERO = worker(0, 0, 0, 0, NO_DROPS)


def known_ctx():
    """Two callbacks.  The first is a chain: drains of 3, 2 and 1
    datagrams (the last ends it), so two drains followed another.  The
    second finds 2 and then nothing: one more chained drain, one empty
    ``recvmmsg``.  Five ``recvmmsg`` calls, four that brought datagrams;
    five drains, three of them chained; the native and the Python lanes
    and the balancer's dropped 2, 1 and 4 answers."""
    after = worker(recv_calls=5, batches=4, events=2, chained=3,
                   drops={"native": 2, "python": 1, "balancer": 4})
    return {"before": {"at": 10.0, "workers": [ZERO]},
            "after": {"at": 30.0, "workers": [after]}}


@pytest.mark.parametrize("name,want", [
    ("udp_chained_share", 100.0 * 3 / 5),
    ("udp_empty_recv_share", 100.0 * 1 / 5),
    ("udp_send_drops", 7.0),
])
def test_reader_on_a_known_chain(name, want):
    assert readers()[name].read(known_ctx()) == pytest.approx(want)


def test_no_drop_reads_zero_and_not_none():
    ctx = known_ctx()
    ctx["after"]["workers"] = [worker(5, 4, 2, 3, NO_DROPS)]
    assert readers()["udp_send_drops"].read(ctx) == 0.0


def test_workers_are_summed_before_the_share_is_taken():
    ctx = known_ctx()
    # a second worker that never chained: 10 callbacks of one drain each
    ctx["before"]["workers"].append(ZERO)
    ctx["after"]["workers"].append(worker(10, 10, 10, 0, NO_DROPS))
    got = readers()
    assert got["udp_chained_share"].read(ctx) == pytest.approx(
        100.0 * 3 / 15)
    assert got["udp_empty_recv_share"].read(ctx) == pytest.approx(
        100.0 * 1 / 15)


def test_the_parent_commit_reads_null_for_the_new_counters():
    """The parent has ``udp-recv`` and the batch histogram, so the empty
    share is read there too; it has neither new counter."""
    ctx = without(without(known_ctx(), CHAINED), DROPS)
    got = readers()
    assert got["udp_chained_share"].read(ctx) is None
    assert got["udp_send_drops"].read(ctx) is None
    assert got["udp_empty_recv_share"].read(ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("name", THREE)
@pytest.mark.parametrize("ctx", [
    {}, {"before": None, "after": None},
    {"before": {"at": 0.0, "workers": []},
     "after": {"at": 30.0, "workers": []}},
    {"before": {"at": 0.0, "workers": [{"metrics": "", "status": {}}]},
     "after": {"at": 30.0, "workers": [{"metrics": "", "status": {}}]}},
    parent_ctx(),
], ids=["empty", "untraced", "no-workers", "blank-scrapes",
        "before-the-ledger"])
def test_reader_gives_none_where_there_is_nothing_to_read(name, ctx):
    assert readers()[name].read(ctx) is None


def test_the_manifest_states_what_the_readers_state():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    better = {"udp_chained_share": "higher", "udp_empty_recv_share": "lower",
              "udp_send_drops": "lower"}
    for name in THREE:
        module, entry = readers()[name], by_name[name]
        assert entry["unit"] == module.UNIT
        assert entry["layer"] == module.LAYER == "kernel socket path"
        assert entry["moves"] == module.MOVES == "p50_us"
        assert entry["source"] == "program_counter"
        assert entry["better"] == better[name]
        # by name: the cells whose workers read UDP sockets of their own
        # and are not replaced in the window (not the rolled cell); the
        # instances behind the balancer are fed by its link, which chains
        # no drains and makes no ``recvmmsg``, but whose direct-return send
        # (``bal_flush``) counts its drops under the lane ``balancer``
        assert entry["workloads"] == [
            "hosts_zipf_open60", "services_srv_open60",
            "hosts_a_aaaa_open60", "services_srv_edns"] + (
            ["hosts_zipf_balancer_open60"] if name == "udp_send_drops"
            else [])
        assert set(entry["workloads"]) <= set(cells)
    # appended, in this order, behind everything the benchmark had then
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(THREE[0])
    assert names[at:at + 3] == list(THREE) and at >= 44
