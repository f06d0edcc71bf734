"""Tests of the spawn and of the group (``run.py`` ``entry_argv``,
``Group``, ``SupervisorGroup``, ``BalancerGroup``): the command a
configuration's ``entry`` names, a balancer's group read from a run
directory laid out by hand, the drain that drops the query log, what a
balancer configuration refuses, what the manifest lists for the cell behind
the balancer, and a CPU rehearsal of that topology at the ``tiny`` size
through the real ``bin/binder-topology``."""
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading

import pytest

from test_benchmark import BENCH, ROOT, manifest, rehearse

sys.path.insert(0, BENCH)
import run  # noqa: E402

CELL = "hosts_zipf_balancer_open60"
CONFIG = "dc-hosts-100k-x4-balancer"
#: the balancer's own account (its stats socket, its pid's CPU) ...
FRONT = ("balancer_cpu_share", "balancer_us_per_query",
         "balancer_cache_share", "direct_return_share", "backend_balance",
         "balancer_syscalls_per_query")
#: ... and the instances' end of the link: the direct-return lane's sends
LINK = ("direct_send_us_per_answer", "direct_answers_per_send")
#: readers of a worker's own UDP socket and its reader (``udp-recv`` and
#: the batch counters are the reader's; the link's read is under no
#: stage), of the lanes ``udp`` and ``tcp``, of the span ``native-serve``
#: (the link's native serve opens none) and of the supervisor's shards
NOT_THE_LINKS = ("shard_balance", "syscalls_per_answer", "recv_batch_mean",
                 "socket_us_per_answer", "udp_chained_share",
                 "udp_empty_recv_share", "native_us_per_answer",
                 "event_hold_p99_us")
#: counted where every lane passes, the link's too: the native core's hit
#: accounting, ``_handle_raw``, the log's one writer, ``bal_flush``
THE_LINKS_TOO = ("native_serve_share", "native_cache_share",
                 "python_serve_share", "python_us_per_query",
                 "ingress_us_per_query", "unnamed_ingress_share",
                 "log_us_per_answer", "log_direct_share",
                 "log_write_bytes_mean", "udp_send_drops")


def load(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


# -- the spawn --

@pytest.mark.parametrize("name", ["dc-hosts-100k-x4", "dc-services-x4",
                                  "dc-hosts-100k-x4-rolling"])
def test_the_argv_of_a_supervisor_configuration_is_what_it_always_was(name):
    config = load("configs", name + ".json")
    assert config.get("topology", "supervisor") == "supervisor"
    assert run.entry_argv(config, "/x/out/config.json", "/x/out") == [
        sys.executable, "-u", "-m", "binder_tpu.main", "-f",
        "/x/out/config.json", "--shards", "4"]


def test_the_argv_of_the_balancer_configuration_names_binder_topology():
    config = load("configs", CONFIG + ".json")
    assert config["topology"] == "balancer"
    assert run.entry_argv(config, "/x/config.json", "/x/run", 25301) == [
        sys.executable, "bin/binder-topology", "start", "-n", "4", "-c",
        "/x/config.json", "-D", "/x/run", "-p", "0", "-B", "25301", "--bind",
        "127.0.0.1"]
    assert sorted(run.TOPOLOGIES) == ["balancer", "supervisor"]
    # upstream's 5301 is not kept, and the file says so
    assert "base_port" not in config and "5301" in config["assumed"]["ports"]


def test_the_members_ports_are_free_ones_and_a_taken_port_is_passed_over(
        monkeypatch):
    """A port of the members' range, or the metrics port 1000 above one,
    that something holds (UDP or TCP) rules a base out."""
    holders = []
    for kind, offset in ((socket.SOCK_STREAM, 1), (socket.SOCK_DGRAM, 1002)):
        while True:
            base = run.free_base_port(3)
            sock = socket.socket(socket.AF_INET, kind)
            try:
                sock.bind(("127.0.0.1", base + offset))
            except OSError:
                sock.close()
                continue
            holders.append((base, sock))
            break
    free = run.free_base_port(3)
    draws = iter([base for base, _ in holders] + [free])

    class Draw:
        def randrange(self, low, high):
            assert (low, high) == (20000, 30000)
            return next(draws)
    monkeypatch.setattr(run.random, "SystemRandom", Draw)
    try:
        assert run.free_base_port(3) == free
    finally:
        for _, sock in holders:
            sock.close()
    # nothing free in 200 draws: the run ends at the start and says why
    monkeypatch.setattr(Draw, "randrange", lambda self, low, high: free)
    holder = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    holder.bind(("127.0.0.1", free))
    try:
        with pytest.raises(SystemExit) as refused:
            run.free_base_port(1)
    finally:
        holder.close()
    assert "FAILED in start" in str(refused.value)
    assert "free ports" in str(refused.value)


# -- a balancer's group, from a run directory laid out by hand --

def test_a_balancer_group_is_read_from_its_run_directory(tmp_path):
    rundir = tmp_path / "r"
    (rundir / "state").mkdir(parents=True)
    (rundir / "sockets").mkdir()
    (rundir / "metric_ports").write_text("6301 6302 6303\n")
    for port, pid in ((5301, 111), (5302, 222), (5303, 333)):
        (rundir / "state" / f"binder-{port}.pid").write_text(f"{pid}\n")
    assert run.rundir_members(str(rundir), [5301, 5302, 5303]) == [
        {"shard": 0, "metrics_port": 6301, "pid": 111},
        {"shard": 1, "metrics_port": 6302, "pid": 222},
        {"shard": 2, "metrics_port": 6303, "pid": 333}]
    # an instance whose pid file is gone has no pid
    (rundir / "state" / "binder-5302.pid").unlink()
    assert run.rundir_members(str(rundir), [5301, 5302])[1]["pid"] is None
    assert run.read_int(str(rundir / "balancer.pid")) is None

    dump = {"udp_queries": 7, "backends": [{"id": 0, "healthy": True}]}
    server = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    server.bind(str(rundir / "sockets" / ".balancer.stats"))
    server.listen(1)

    def serve():
        conn, _ = server.accept()
        conn.sendall(json.dumps(dump).encode())
        conn.close()
    thread = threading.Thread(target=serve)
    thread.start()
    try:
        assert run.balancer_stats(str(rundir)) == dump
    finally:
        thread.join(5)
        server.close()


def test_the_balancer_readers_read_its_stats_between_the_scrapes():
    def front(cpu_s, queries, direct, hits, handed, pid=9):
        return {"pid": pid, "cpu_s": cpu_s, "stats": {
            "udp_queries": queries, "tcp_queries": 0, "cache_hits": hits,
            "syscalls": queries // 4,
            "direct_forwards": direct, "backends": [
                {"path": f"/s/{5301 + n}", "forwarded": f}
                for n, f in enumerate(handed)]}}
    ctx = {"before": {"at": 10.0, "balancer": front(1.0, 1000, 900, 0,
                                                    [500, 400])},
           "after": {"at": 60.0, "balancer": front(26.0, 1001000, 990900, 0,
                                                   [250500, 750400])}}
    got = {name: run.layer_readers()[name].read(ctx) for name in FRONT}
    assert got["balancer_cpu_share"] == pytest.approx(50.0)
    assert got["balancer_us_per_query"] == pytest.approx(25.0)
    assert got["balancer_cache_share"] == 0.0
    assert got["direct_return_share"] == pytest.approx(99.0)
    assert got["backend_balance"] == pytest.approx(1 / 3)
    assert got["balancer_syscalls_per_query"] == pytest.approx(0.25)
    # a supervisor's scrape, an untraced run, a balancer that was replaced
    ctx["after"]["balancer"]["pid"] = 10
    for empty in ({}, {"before": None, "after": None}, ctx,
                  {"before": {"at": 0.0, "supervisor": {}, "workers": []},
                   "after": {"at": 9.0, "supervisor": {}, "workers": []}}):
        for name in FRONT:
            assert run.layer_readers()[name].read(empty) is None


def test_the_link_readers_read_the_direct_return_sends_of_the_instances():
    """Two instances behind a balancer: 1,000 answers left in 400 sends
    that took 0.05 s together.  In front of a supervisor, or untraced,
    there is nothing to read."""
    from test_spans import ZERO, worker
    front = {"pid": 9, "cpu_s": 1.0, "stats": {}}
    sent = [worker(sums={"udp-send": 0.03}, counts={"udp-send": 300},
                   counters={("binder_requests_completed", '{type="A"}'): 600,
                             ("binder_udp_datagrams", '{dir="out"}'): 600}),
            worker(sums={"udp-send": 0.02}, counts={"udp-send": 100},
                   counters={("binder_requests_completed", '{type="A"}'): 400,
                             ("binder_udp_datagrams", '{dir="out"}'): 400})]
    ctx = {"before": {"at": 10.0, "balancer": front,
                      "workers": [ZERO, ZERO]},
           "after": {"at": 60.0, "balancer": front, "workers": sent}}
    got = {name: run.layer_readers()[name] for name in LINK}
    assert got["direct_send_us_per_answer"].read(ctx) == pytest.approx(50.0)
    assert got["direct_answers_per_send"].read(ctx) == pytest.approx(2.5)
    del ctx["before"]["balancer"], ctx["after"]["balancer"]
    for empty in ({}, {"before": None, "after": None}, ctx):
        for name in LINK:
            assert got[name].read(empty) is None


# -- the drain --

def test_the_drain_drops_the_query_lines_and_keeps_the_control_lines(
        tmp_path):
    group = run.Group({"shards": 1}, "unused", str(tmp_path))
    fifo = str(tmp_path / "instance.log")
    reader, keep = run.open_fifo(fifo)
    group.drain(reader, member=3)
    os.close(reader)
    # the writer opens it as instance_adjust does, and does not block
    writer = os.open(fifo, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    query = json.dumps({"msg": "DNS query", "name": "a.foo.com"})
    for n in range(2000):
        os.write(writer, (query + "\n").encode())
        if n == 1000:
            os.write(writer, (json.dumps(
                {"msg": "chaos: injected watch-storm n=8"}) + "\n").encode())
    os.write(writer, b"Traceback (most recent call last):\n")
    os.close(writer)
    os.close(keep)                      # now the drain sees the end
    group.end_drains(kill=False)
    group._log.close()
    (match, arrived, member), = group.matches(r"^chaos: injected (\S+)")
    assert match.group(1) == "watch-storm" and member == 3
    assert group.find_msg(r"^chaos")[1] == arrived
    assert group.find_msg(r"DNS query") is None
    log = open(os.path.join(str(tmp_path), "server.log")).read()
    assert log.count("\n") == 2 and "DNS query" not in log
    assert "Traceback" in log


# -- what a balancer configuration refuses --

def test_skew_replica_is_refused_for_a_balancer_configuration():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--cpu", "--dir",
         "benchmark/tests/tiny", "--workload", "tiny_balancer", "--seed",
         "1", "--seconds", "1", "--trace", "0", "--break", "skew-replica"],
        cwd=ROOT, text=True, timeout=60, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    assert proc.returncode != 0
    assert "FAILED in start" in proc.stderr
    assert "skew-replica" in proc.stderr and "mutation log" in proc.stderr
    assert "REHEARSAL" not in proc.stdout


def test_an_event_is_refused_for_a_balancer_configuration():
    with pytest.raises(SystemExit) as refused:
        run.BalancerGroup.check_events([
            {"at_s": 2, "signal": "SIGHUP", "to": "supervisor"}])
    assert "balancer topology" in str(refused.value)
    run.BalancerGroup.check_events([])


# -- the manifest --

def test_the_manifest_lists_for_the_cell_what_its_readers_can_read():
    m = manifest()
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic=CELL, chips=1)
    (entry,) = [c for c in m["configs"] if c["name"] == CONFIG]
    config, hosts = load("configs", CONFIG + ".json"), load(
        "configs", "dc-hosts-100k-x4.json")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert entry["reduced"] == ["hosts"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # the hosts deployment's zone and posture, in another topology
    for key in ("hosts", "racks", "subtree", "services", "base_config",
                "shards", "posture_overrides", "reduced"):
        assert config[key] == hosts[key], key
    # (the same write, later: the group can take 13 s to be ready, and the
    # written names are asked, and must be refused, before the write)
    assert config["chaos"] == dict(hosts["chaos"], mutate_at_s=18)
    assert {"stores", "return_lane", "shards", "write_time", "ports"} \
        <= set(config["assumed"])
    workload, steady = load("workloads", CELL + ".json"), load(
        "workloads", "hosts_zipf_open60.json")
    # the hosts cell's traffic to the letter, at a rate of its own
    for key in steady:
        if key not in ("name", "config", "why", "rate_per_s",
                       "expect_per_s", "rate_from"):
            assert workload[key] == steady[key], key
    assert workload["rate_per_s"] == workload["expect_per_s"]
    assert workload["rate_per_s"] % 2800 == 0
    # the sweep's table, the step that did not hold and the generator's
    # lateness at every step are in the file
    rate_from = workload["rate_from"]
    assert isinstance(rate_from, dict) and rate_from["steps"]
    assert {"rate_per_s", "failed", "attempted", "in_flight_first_2s",
            "in_flight_last_5s", "holds", "gen_late_p99_us"} \
        <= set(rate_from["steps"][0])
    assert workload["rate_per_s"] in [s["rate_per_s"]
                                      for s in rate_from["steps"]]
    assert f"{workload['rate_per_s']:,}/s" in cell["why"]
    got = run.layer_readers()
    listed = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    for p in m["per_layer"]:
        if p["name"] in FRONT + LINK:
            module = got[p["name"]]
            assert p["workloads"] == [CELL]
            assert (p["unit"], p["layer"], p["moves"]) == (
                module.UNIT, module.LAYER, module.MOVES) == (
                module.UNIT, "balancer front end", "p50_us")
        elif CELL in p["workloads"]:
            assert p["workloads"][-1] == CELL       # appended, nothing else
            assert "hosts_zipf_open60" in p["workloads"]
    assert set(FRONT + LINK) <= listed and not listed & set(NOT_THE_LINKS)
    assert {"gen_late_p99_us", "tail_p90_us", "tail_p99_us", "gen_stop_ms",
            "voided_share", "ready_s", "seed_s", "loop_busy_share",
            "busy_unnamed_share", "loop_lag_p99_ms"} \
        | set(THE_LINKS_TOO) <= listed
    # every reader of the steady hosts cell is listed or named above with
    # its reason; nothing of a roll, of the services zone or of the A/AAAA
    # mix is
    assert all(CELL in p["workloads"] or "hosts_zipf_open60"
               not in p["workloads"] or p["name"] in NOT_THE_LINKS
               for p in m["per_layer"])


# -- the rehearsal, through the real bin/binder-topology --

def leftovers() -> list:
    """Processes of a topology that are still there (a zombie is one)."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().replace(b"\0", b" ").decode("utf-8", "replace")
            with open(f"/proc/{pid}/stat") as f:
                comm = f.read().split("(", 1)[1].rsplit(")", 1)[0]
        except OSError:
            continue
        if comm == "mbalancer" or (comm.startswith("python") and
                                   "binder_tpu.main" in argv and
                                   "/sockets/" in argv):
            found.append((pid, comm, argv))
    return found


def test_rehearsal_behind_the_balancer_is_correct_and_leaves_nothing():
    result = rehearse("tiny_balancer", 2**31 + 50, 1, seconds=3)
    assert result["correct"], result["stdout"][-3000:]
    assert result["failed"] == 0 and result["attempted"] > 3000
    compared = result["compared"]
    # the clean stop's row has one name, whatever stops the group
    for row in ("supervisor_exit_code","workers_not_seen_answering", "workers_not_serving_the_write",
                "written_names_served_before_the_write", "orphan_processes"):
        assert compared[row] == {"value": 0, "limit": 0}
    # (two instances and the balancer)
    assert "orphan check over 3 worker pids" in result["stdout"]
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(FRONT + LINK) <= set(metrics)
    assert 1 <= metrics["direct_answers_per_send"] <= 64
    assert metrics["direct_send_us_per_answer"] > 0
    # both instances served, nearly every answer went straight back (the
    # TC=1 refetches come over TCP, which the balancer relays), and the
    # balancer's cache was never asked
    assert metrics["backend_balance"] > 0.5
    assert 90 < metrics["direct_return_share"] <= 100
    assert metrics["balancer_cache_share"] == 0
    assert 0 < metrics["balancer_cpu_share"] < 100
    assert metrics["native_serve_share"] > 90
    assert metrics["ready_s"] > 0 and metrics["seed_s"] > 0
    stages = dict(result["breakdown"]["idle_gaps"])
    assert stages["balancer cpu"] > 0
    # nothing is left: no process, no log of queries, nothing large
    assert leftovers() == []
    out = os.path.join(BENCH, "out", "tiny_balancer")
    sizes = {os.path.relpath(os.path.join(d, f), out):
             os.path.getsize(os.path.join(d, f))
             for d, _, files in os.walk(out) for f in files}
    assert max(sizes.values()) < 1 << 20, sizes
    log = open(os.path.join(out, "server.log")).read()
    assert "DNS query" not in log
    assert log.count("chaos: injected watch-storm") == 2
    assert "topology down" in open(os.path.join(out, "topology.log")).read()
    # the run directory went with the run, the balancer's log was kept
    assert "listening on 127.0.0.1" in open(
        os.path.join(out, "balancer.log")).read()
    assert not [d for d in os.listdir(out) + os.listdir(
        tempfile.gettempdir()) if d.startswith("run-")]


@pytest.mark.parametrize("cell", ["tiny_balancer", "tiny_hosts_balancer"])
@pytest.mark.parametrize("broken", ["fixture-address", "reference-address"])
def test_rehearsal_behind_the_balancer_broken_is_not_correct(broken, cell):
    """In the zone with services one member's address is altered; in the
    zone of hosts alone, which the program makes by its own formula, the
    program is given one rack more than the reference knows
    (``fixture-address``: wrong rcodes), or the reference another address
    for the name asked most (``reference-address``: mismatching answers)."""
    result = rehearse(cell, 2**31 + 51, 0, broken)
    assert result["correct"] is False
    outside = {name for name, row in result["compared"].items()
               if row["value"] > row["limit"]}
    assert outside & {"window_answers_mismatching",
                      "window_answers_wrong_rcode_or_count"}, outside
    assert {"asks_mismatching_before_window",
            "asks_mismatching_after_window"} <= outside
    assert leftovers() == []
