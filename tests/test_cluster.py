"""Multi-host cluster tests: two real host processes on localhost joined
over TCP — the DCN-path analog of the reference pointing
``ray.init(address="auto")`` at a multi-node Ray cluster (SURVEY §7 M3).

The runtime context is a per-process singleton, so head and worker each run
in their own subprocess; the test asserts on their printed verdicts. This
exercises, with real process and socket boundaries:

* cluster bootstrap (registry, per-host agents + store servers),
* cross-host task scattering (map/reduce on both hosts' pools),
* cross-host object fetch (reducer pulling a foreign mapper partition;
  trainer pulling foreign reducer outputs),
* cluster-wide named-actor discovery (the queue actor found via the
  registry).
"""

import os
import subprocess
import sys
import time

import pytest

# Subprocess-heavy cluster tests stay in the slow tier; the scheduler
# unit tests below (fake in-process agents, no subprocesses) run in
# tier-1.
slow = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Echo:
    """Module-level so the spawned actor process can unpickle it."""

    def echo(self, x):
        return x

HEAD_SCRIPT = r"""
import json, os, sys, time
sys.path.insert(0, {repo!r})
from ray_shuffling_data_loader_tpu import runtime, ShufflingDataset
from ray_shuffling_data_loader_tpu.data_generation import generate_data

ctx = runtime.init_cluster(advertise_host="127.0.0.1", num_workers=2)
with open({addr_file!r} + ".tmp", "w") as f:
    f.write(ctx.cluster.address)
os.rename({addr_file!r} + ".tmp", {addr_file!r})

# Wait for the worker host to join.
deadline = time.time() + 60
while len(ctx.cluster.registry.call("hosts")) < 2:
    if time.time() > deadline:
        print("VERDICT: FAIL worker never joined", flush=True)
        sys.exit(1)
    time.sleep(0.2)

filenames, _ = generate_data(
    num_rows=2000, num_files=4, num_row_groups_per_file=1,
    max_row_group_skew=0.0, data_dir={data_dir!r},
)
ds = ShufflingDataset(
    filenames, num_epochs=2, num_trainers=1, batch_size=250, rank=0,
    num_reducers=4, seed=11, queue_name="q-cluster",
)
ok = True
for epoch in range(2):
    ds.set_epoch(epoch)
    keys = sorted(k for b in ds for k in b["key"].tolist())
    if keys != list(range(2000)):
        ok = False
        print(f"VERDICT: FAIL epoch {{epoch}} keys wrong", flush=True)

# Both hosts' agents must have executed tasks (round-robin scatter).
hosts = ctx.cluster.registry.call("hosts")
from ray_shuffling_data_loader_tpu.runtime.actor import ActorHandle
counts = {{
    hid: ActorHandle(tuple(info["agent"])).call("agent_stats")["completed"]
    for hid, info in hosts.items()
}}
print(f"agent task counts: {{counts}}", flush=True)
if len(counts) != 2 or not all(c > 0 for c in counts.values()):
    ok = False
    print("VERDICT: FAIL tasks not scattered across hosts", flush=True)

# Named-actor discovery through the registry.
if runtime.resolve_actor("q-cluster") is None:
    ok = False
    print("VERDICT: FAIL named actor not in registry", flush=True)

print("VERDICT: " + ("PASS" if ok else "FAIL"), flush=True)
runtime.shutdown()
"""

WORKER_SCRIPT = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.runtime import cluster

deadline = time.time() + 60
while not os.path.exists({addr_file!r}):
    if time.time() > deadline:
        sys.exit(2)
    time.sleep(0.1)
with open({addr_file!r}) as f:
    address = f.read().strip()
ctx = runtime.init(address=address, num_workers=2)
print(f"joined {{ctx.cluster.host_id}}", flush=True)
cluster.serve_forever()
runtime.shutdown()
"""


@slow
def test_tcp_actor_requires_cluster_token(tmp_path, monkeypatch):
    """TCP endpoints speak pickle, so unauthenticated peers must be dropped
    before their first frame is deserialized. Auth is an HMAC
    challenge-response (transport.py): the server sends a nonce and only a
    peer holding the cluster secret can answer — the secret itself never
    crosses the wire."""
    import pickle
    import socket
    import struct

    from ray_shuffling_data_loader_tpu.runtime import actor as actor_mod

    monkeypatch.setenv("RSDL_CLUSTER_TOKEN", "sekrit-token")

    handle = actor_mod.spawn_actor(
        Echo, runtime_dir=str(tmp_path), host="127.0.0.1"
    )
    try:
        # Authorized: the handle answers the server's challenge.
        assert handle.call("echo", 41) == 41

        # Unauthorized: a peer that ignores the challenge and sends a raw
        # request frame is dropped without a reply. The server's challenge
        # frame must not contain the secret.
        _, host, port = handle.address
        sock = socket.create_connection((host, port), timeout=5)
        try:
            sock.settimeout(5)
            header = sock.recv(8)
            (length,) = struct.unpack("<Q", header)
            challenge = sock.recv(length)
            assert challenge.startswith(b"RSDLAUTH")
            assert b"sekrit-token" not in challenge  # secret stays local
            payload = pickle.dumps((1, "echo", (42,), {}, False))
            sock.sendall(struct.pack("<Q", len(payload)) + payload)
            assert sock.recv(1) == b""  # server closed without answering
        finally:
            sock.close()

        # Wrong token: the digest won't verify; also dropped.
        monkeypatch.setenv("RSDL_CLUSTER_TOKEN", "wrong")
        from ray_shuffling_data_loader_tpu.runtime.actor import ActorHandle

        intruder = ActorHandle(handle.address)
        assert not intruder.ping(timeout=5)
        monkeypatch.setenv("RSDL_CLUSTER_TOKEN", "sekrit-token")
    finally:
        handle.terminate()


FAILOVER_HEAD_SCRIPT = r"""
import json, os, sys, time
sys.path.insert(0, {repo!r})
from ray_shuffling_data_loader_tpu import runtime, ShufflingDataset
from ray_shuffling_data_loader_tpu.data_generation import generate_data

ctx = runtime.init_cluster(advertise_host="127.0.0.1", num_workers=2)
with open({addr_file!r} + ".tmp", "w") as f:
    f.write(ctx.cluster.address)
os.rename({addr_file!r} + ".tmp", {addr_file!r})

deadline = time.time() + 60
while len(ctx.cluster.registry.call("hosts")) < 2:
    if time.time() > deadline:
        print("VERDICT: FAIL worker never joined", flush=True)
        sys.exit(1)
    time.sleep(0.2)
# Signal the test to SIGKILL the worker, then wait for it to be gone.
open({joined_file!r}, "w").close()
while os.path.exists({joined_file!r}):
    time.sleep(0.1)

filenames, _ = generate_data(
    num_rows=1500, num_files=3, num_row_groups_per_file=1,
    max_row_group_skew=0.0, data_dir={data_dir!r},
)
# The membership table still lists the dead host; the scheduler must hit
# it, drop it, evict it, and reroute every task onto this host.
ds = ShufflingDataset(
    filenames, num_epochs=1, num_trainers=1, batch_size=250, rank=0,
    num_reducers=3, seed=13, queue_name="q-failover",
)
ds.set_epoch(0)
keys = sorted(k for b in ds for k in b["key"].tolist())
ok = keys == list(range(1500))
if not ok:
    print("VERDICT: FAIL keys wrong after failover", flush=True)
hosts = ctx.cluster.registry.call("hosts")
if len(hosts) != 1:
    ok = False
    print(f"VERDICT: FAIL dead host not evicted: {{list(hosts)}}", flush=True)
print("VERDICT: " + ("PASS" if ok else "FAIL"), flush=True)
runtime.shutdown()
"""


@slow
def test_dead_host_failover(tmp_path):
    """A worker host that joined and then died (SIGKILL — no unregister)
    must not break the run: the scheduler drops the dead agent, evicts the
    host from membership, and reroutes its tasks (SURVEY §5: the reference
    has essentially no failure handling; this is new capability)."""
    addr_file = str(tmp_path / "head_address")
    joined_file = str(tmp_path / "worker_joined")
    data_dir = str(tmp_path / "data")
    env = dict(
        os.environ, RSDL_ADVERTISE_HOST="127.0.0.1", JAX_PLATFORMS="cpu"
    )
    head_log = tmp_path / "head.log"
    worker_log = tmp_path / "worker.log"
    with open(head_log, "w") as hf, open(worker_log, "w") as wf:
        head = subprocess.Popen(
            [sys.executable, "-c", FAILOVER_HEAD_SCRIPT.format(
                repo=_REPO,
                addr_file=addr_file,
                joined_file=joined_file,
                data_dir=data_dir,
            )],
            stdout=hf,
            stderr=subprocess.STDOUT,
            env=env,
        )
        worker = subprocess.Popen(
            [sys.executable, "-c", WORKER_SCRIPT.format(
                repo=_REPO, addr_file=addr_file
            )],
            stdout=wf,
            stderr=subprocess.STDOUT,
            env=env,
        )
        try:
            deadline = time.time() + 120
            while not os.path.exists(joined_file):
                assert time.time() < deadline, "worker never joined"
                assert head.poll() is None, "head died early"
                time.sleep(0.2)
            worker.kill()
            worker.wait()
            os.unlink(joined_file)
            head.wait(timeout=180)
        finally:
            head.kill()
            worker.kill()
            head.wait()
            worker.wait()

    head_out = head_log.read_text()
    assert "VERDICT: PASS" in head_out, (
        f"head output:\n{head_out}\n--- worker output:\n"
        f"{worker_log.read_text()}"
    )


def test_unregister_host_sweeps_actor_names():
    """ISSUE 10 satellite: a host's departure (drain or eviction) must
    sweep the actor-name records pointing at it — a stale record would
    hand every later lookup a dead address that times out per call
    instead of failing fast into the retry path. Records carrying the
    departed host_id are swept; legacy records (no host_id) are swept
    only on an exact service-address match; other hosts' names
    survive."""
    from ray_shuffling_data_loader_tpu.runtime.cluster import (
        ClusterRegistry,
    )

    reg = ClusterRegistry()
    reg.register_host(
        "h1", ("tcp", "10.0.0.1", 700), ("tcp", "10.0.0.1", 701), 2
    )
    reg.register_host(
        "h2", ("tcp", "10.0.0.2", 700), ("tcp", "10.0.0.2", 701), 2
    )
    # An actor placed ON h1 (host_id recorded), one on h2, one legacy
    # record whose address IS h1's agent endpoint, and one legacy
    # record on h1's IP but an unrelated port (a different session on
    # the same machine — must NOT be swept).
    reg.register_actor("q1", ("tcp", "10.0.0.1", 710), 11, host_id="h1")
    reg.register_actor("q2", ("tcp", "10.0.0.2", 710), 12, host_id="h2")
    reg.register_actor("legacy-agent", ("tcp", "10.0.0.1", 700), 13)
    reg.register_actor("same-ip-other", ("tcp", "10.0.0.1", 999), 14)

    reg.unregister_host("h1")
    assert reg.lookup_actor("q1") is None
    assert reg.lookup_actor("legacy-agent") is None
    assert reg.lookup_actor("q2") is not None
    assert reg.lookup_actor("same-ip-other") is not None
    assert sorted(reg.hosts()) == ["h2"]
    # Unregistering an unknown host is a no-op, not an error.
    reg.unregister_host("h1")


def test_cluster_scheduler_locality_choice(monkeypatch):
    """Unit: the scheduler places a task on the host owning the most input
    rows; no owners / unknown owner / disabled env -> no preference."""
    from ray_shuffling_data_loader_tpu.runtime.cluster import ClusterScheduler
    from ray_shuffling_data_loader_tpu.runtime.store import ObjectRef

    class FakeAgent:
        def __init__(self, address):
            self.address = address

    a = FakeAgent(("tcp", "hostA", 1))
    b = FakeAgent(("tcp", "hostB", 1))
    sched = ClusterScheduler(
        [a, b],
        {("tcp", "hostA", 9): a, ("tcp", "hostB", 9): b},
    )
    try:
        refs = [
            ObjectRef("x", 100, owner=("tcp", "hostA", 9), rows=(0, 10)),
            ObjectRef("y", 100, owner=("tcp", "hostB", 9), rows=(0, 90)),
        ]
        assert sched._locality_agent(refs) is b
        # Whole-segment refs weigh by nbytes.
        big = ObjectRef("z", 10_000, owner=("tcp", "hostA", 9))
        assert sched._locality_agent([big]) is a
        # Ownerless refs give no preference; unknown owners neither.
        assert sched._locality_agent([ObjectRef("w", 5)]) is None
        assert (
            sched._locality_agent(
                [ObjectRef("v", 5, owner=("tcp", "gone", 9))]
            )
            is None
        )
        monkeypatch.setenv("RSDL_DISABLE_LOCALITY", "1")
        assert sched._locality_agent(refs) is None
    finally:
        sched.shutdown()


def test_scheduler_confirms_death_before_evicting():
    """A transient connection error (ActorHandle wraps every
    ConnectionError/OSError into ActorDiedError) must NOT evict a live
    host: the scheduler pings on a fresh connection and retries. Only an
    unreachable agent is dropped (ADVICE r1, medium)."""
    from ray_shuffling_data_loader_tpu.runtime.actor import ActorDiedError
    from ray_shuffling_data_loader_tpu.runtime.cluster import ClusterScheduler

    class FlakyAgent:
        """First call hits a connection reset; the host is alive."""

        address = ("tcp", "flaky", 1)

        def __init__(self):
            self.calls = 0

        def call(self, method, *args):
            self.calls += 1
            if self.calls == 1:
                raise ActorDiedError("transient reset")
            return "ok"

        def ping(self, timeout=None):
            return True

    class DeadAgent:
        address = ("tcp", "dead", 1)

        def call(self, method, *args):
            raise ActorDiedError("down")

        def ping(self, timeout=None):
            return False

    flaky = FlakyAgent()
    sched = ClusterScheduler([flaky])
    try:
        ok, result = sched._submit_once(flaky, None, (), {})
        assert ok and result == "ok"
        assert sched.agent_addresses == {flaky.address}  # NOT evicted
    finally:
        sched.shutdown()

    dead = DeadAgent()
    sched = ClusterScheduler([flaky, dead])
    try:
        ok, _ = sched._submit_once(dead, None, (), {})
        assert not ok
        assert sched.agent_addresses == {flaky.address}  # dead one dropped
    finally:
        sched.shutdown()


def test_ping_ladder_escalates_before_evicting():
    """A loaded-but-alive host can miss the short pings and only answer a
    long one — the ladder must keep escalating (5 s -> 10 s -> 20 s)
    instead of evicting on the first miss (ISSUE 3 satellite: ladder
    false-eviction avoidance, fake in-process agents)."""
    from ray_shuffling_data_loader_tpu.runtime.actor import ActorDiedError
    from ray_shuffling_data_loader_tpu.runtime.cluster import ClusterScheduler

    class LoadedAgent:
        """Submit hits a transient reset; pings shorter than 10 s go
        unanswered (host saturated), longer ones succeed."""

        address = ("tcp", "loaded", 1)

        def __init__(self):
            self.calls = 0
            self.ping_timeouts = []

        def call(self, method, *args):
            self.calls += 1
            if self.calls == 1:
                raise ActorDiedError("transient reset")
            return "ok"

        def ping(self, timeout=None):
            self.ping_timeouts.append(timeout)
            return timeout is not None and timeout >= 10.0

    agent = LoadedAgent()
    sched = ClusterScheduler([agent])
    try:
        ok, result = sched._submit_once(agent, None, (), {})
        assert ok and result == "ok"
        # The ladder escalated past the first (missed) rung before the
        # retry — and the host was NOT evicted.
        assert agent.ping_timeouts[:2] == [5.0, 10.0]
        assert sched.agent_addresses == {agent.address}
    finally:
        sched.shutdown()


def test_drop_agent_updates_membership_and_fires_callback():
    """``_drop_agent``: the agent leaves the rotation exactly once, the
    ``on_agent_dead`` callback (the membership-table eviction hook) fires
    with the dead handle, and a raising callback never breaks the
    scheduler."""
    from ray_shuffling_data_loader_tpu.runtime.cluster import ClusterScheduler

    class FakeAgent:
        def __init__(self, name):
            self.address = ("tcp", name, 1)

    a, b = FakeAgent("a"), FakeAgent("b")
    sched = ClusterScheduler([a, b])
    try:
        evicted = []
        sched.on_agent_dead = evicted.append
        sched._drop_agent(a)
        assert evicted == [a]
        assert sched.agent_addresses == {b.address}
        # Idempotent: a racing re-drop neither corrupts the rotation nor
        # double-fires the eviction callback (one eviction per dead
        # host, not one per racing task).
        sched._drop_agent(a)
        assert sched.agent_addresses == {b.address}
        assert evicted == [a]

        # A callback that raises must be swallowed (eviction is
        # best-effort bookkeeping; the failover itself already happened).
        def boom(agent):
            raise RuntimeError("registry unreachable")

        sched.on_agent_dead = boom
        sched._drop_agent(b)
        assert sched.agent_addresses == set()
    finally:
        sched.shutdown()


def test_all_agents_dead_raises_actor_died():
    """When every host agent has died, a submit must surface
    ``ActorDiedError`` (bounded failure) — never spin or hang looking
    for a host that will not come back."""
    from ray_shuffling_data_loader_tpu.runtime.actor import ActorDiedError
    from ray_shuffling_data_loader_tpu.runtime.cluster import ClusterScheduler

    class DeadAgent:
        def __init__(self, name):
            self.address = ("tcp", name, 1)

        def call(self, method, *args):
            raise ActorDiedError("down")

        def ping(self, timeout=None):
            return False

    agents = [DeadAgent("d1"), DeadAgent("d2")]
    sched = ClusterScheduler(agents)
    try:
        fut = sched.submit(lambda: None)
        with pytest.raises(ActorDiedError, match="every cluster host"):
            fut.result(timeout=60)
        assert sched.agent_addresses == set()
    finally:
        sched.shutdown()


LOCALITY_HEAD_SCRIPT = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
from ray_shuffling_data_loader_tpu import runtime, ShufflingDataset
from ray_shuffling_data_loader_tpu.data_generation import generate_data
from ray_shuffling_data_loader_tpu.runtime.actor import ActorHandle

ctx = runtime.init_cluster(advertise_host="127.0.0.1", num_workers=2)
with open({addr_file!r} + ".tmp", "w") as f:
    f.write(ctx.cluster.address)
os.rename({addr_file!r} + ".tmp", {addr_file!r})

deadline = time.time() + 60
while len(ctx.cluster.registry.call("hosts")) < 2:
    if time.time() > deadline:
        print("VERDICT: FAIL worker never joined", flush=True)
        sys.exit(1)
    time.sleep(0.2)

# 3 files over 2 hosts: round-robin maps put files 0,2 on the head and
# file 1 on the worker, so the head owns 2/3 of every reducer's input —
# a deterministic skew for the locality scheduler to exploit.
filenames, _ = generate_data(
    num_rows=3000, num_files=3, num_row_groups_per_file=1,
    max_row_group_skew=0.0, data_dir={data_dir!r},
)
ds = ShufflingDataset(
    filenames, num_epochs=1, num_trainers=1, batch_size=500, rank=0,
    num_reducers=4, seed=17, queue_name="q-locality",
)
ds.set_epoch(0)
keys = sorted(k for b in ds for k in b["key"].tolist())
ok = keys == list(range(3000))
if not ok:
    print("VERDICT: FAIL keys wrong", flush=True)
hosts = ctx.cluster.registry.call("hosts")
cross = sum(
    ActorHandle(tuple(info["store"])).call("fetch_stats")["bytes"]
    for info in hosts.values()
)
print(f"CROSS_BYTES: {{cross}}", flush=True)
print("VERDICT: " + ("PASS" if ok else "FAIL"), flush=True)
runtime.shutdown()
"""


def _run_locality_cluster(tmp_path, tag: str, extra_env: dict) -> int:
    addr_file = str(tmp_path / f"head_address_{tag}")
    data_dir = str(tmp_path / f"data_{tag}")
    env = dict(
        os.environ, RSDL_ADVERTISE_HOST="127.0.0.1", JAX_PLATFORMS="cpu"
    )
    env.update(extra_env)
    # Per-"host" shared-memory dirs: on one physical machine both
    # sessions would otherwise share /dev/shm, and get_columns maps a
    # peer's segment directly — zero measured cross-host bytes for BOTH
    # schedules. Separate dirs force every cross-session read through
    # the store servers, the way distinct hosts behave.
    shm_head = f"/dev/shm/rsdl-test-{tag}-head"
    shm_worker = f"/dev/shm/rsdl-test-{tag}-worker"
    head_log = tmp_path / f"head_{tag}.log"
    worker_log = tmp_path / f"worker_{tag}.log"
    import shutil

    with open(head_log, "w") as hf, open(worker_log, "w") as wf:
        head = subprocess.Popen(
            [sys.executable, "-c", LOCALITY_HEAD_SCRIPT.format(
                repo=_REPO, addr_file=addr_file, data_dir=data_dir
            )],
            stdout=hf, stderr=subprocess.STDOUT,
            env=dict(env, RSDL_SHM_DIR=shm_head),
        )
        worker = subprocess.Popen(
            [sys.executable, "-c", WORKER_SCRIPT.format(
                repo=_REPO, addr_file=addr_file
            )],
            stdout=wf, stderr=subprocess.STDOUT,
            env=dict(env, RSDL_SHM_DIR=shm_worker),
        )
        try:
            head.wait(timeout=240)
            worker.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        finally:
            head.kill()
            worker.kill()
            head.wait()
            worker.wait()
            for d in (shm_head, shm_worker):
                shutil.rmtree(d, ignore_errors=True)
    out = head_log.read_text()
    assert "VERDICT: PASS" in out, (
        f"head[{tag}]:\n{out}\n--- worker:\n{worker_log.read_text()}"
    )
    for line in out.splitlines():
        if line.startswith("CROSS_BYTES:"):
            return int(line.split(":")[1])
    raise AssertionError(f"no CROSS_BYTES in head output:\n{out}")


def _cross_host_bytes(tmp_path, tag: str, extra_env: dict) -> int:
    """Bytes the store servers moved between the two hosts in one run. A
    measurement of 0 means the run degenerated — the worker host was
    evicted under CPU saturation and everything ran locally — which says
    nothing about the path under test: retry a couple of times before
    declaring the environment unusable."""
    for attempt in range(3):
        cross = _run_locality_cluster(tmp_path, f"{tag}{attempt}", extra_env)
        if cross > 0:
            return cross
    pytest.skip(
        f"cluster degenerated to a single host in every {tag!r} run "
        "(CPU-saturated environment); the comparison needs two live hosts"
    )


@slow
def test_locality_scheduling_cuts_cross_host_bytes(tmp_path):
    """Two-host cluster, skewed input ownership: locality-aware reduce
    placement must move materially fewer bytes across the DCN than pure
    round-robin (VERDICT r1 item 5)."""
    # With two healthy hosts and skewed ownership, EVERY healthy run moves
    # bytes across hosts: round-robin reduce placement obviously, and the
    # locality run too (file 1 maps on the worker, so even all-reduces-on-
    # head still pulls that partition across); _cross_host_bytes retries
    # a run that moved none.
    rr = _cross_host_bytes(tmp_path, "rr", {"RSDL_DISABLE_LOCALITY": "1"})
    loc = _cross_host_bytes(tmp_path, "loc", {})
    assert loc < rr * 0.7, (
        f"locality={loc} bytes vs round-robin={rr} bytes — "
        "expected a >=30% cross-host reduction"
    )


@slow
def test_two_host_shuffle_over_striped_zerocopy(tmp_path):
    """The exactly-once two-host shuffle with every cross-host read on
    the vectored reply path (``RSDL_TCP_ZEROCOPY``) and striped over two
    streams (``RSDL_TCP_STREAMS``), cluster-wide: each "host" has its
    own shm dir, so the bytes ride the store servers' TCP replies."""
    cross = _cross_host_bytes(
        tmp_path,
        "zc",
        {
            "RSDL_DISABLE_LOCALITY": "1",
            "RSDL_TCP_ZEROCOPY": "1",
            "RSDL_TCP_STREAMS": "2",
        },
    )
    assert cross > 0


@slow
def test_two_host_cluster_shuffle(tmp_path):
    addr_file = str(tmp_path / "head_address")
    data_dir = str(tmp_path / "data")
    env = dict(
        os.environ,
        RSDL_ADVERTISE_HOST="127.0.0.1",
        JAX_PLATFORMS="cpu",
    )

    # Output goes to files, not pipes: spawned actor/pool children inherit
    # the parents' stdout, so pipe EOF would only come when every daemon
    # grandchild exits.
    head_log = tmp_path / "head.log"
    worker_log = tmp_path / "worker.log"
    with open(head_log, "w") as hf, open(worker_log, "w") as wf:
        head = subprocess.Popen(
            [sys.executable, "-c", HEAD_SCRIPT.format(
                repo=_REPO, addr_file=addr_file, data_dir=data_dir
            )],
            stdout=hf,
            stderr=subprocess.STDOUT,
            env=env,
        )
        worker = subprocess.Popen(
            [sys.executable, "-c", WORKER_SCRIPT.format(
                repo=_REPO, addr_file=addr_file
            )],
            stdout=wf,
            stderr=subprocess.STDOUT,
            env=env,
        )
        try:
            head.wait(timeout=240)
            # Worker exits on its own once the head's registry goes away.
            worker.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        finally:
            head.kill()
            worker.kill()
            head.wait()
            worker.wait()

    head_out = head_log.read_text()
    worker_out = worker_log.read_text()
    assert "VERDICT: PASS" in head_out, (
        f"head output:\n{head_out}\n--- worker output:\n{worker_out}"
    )
    assert "joined" in worker_out, worker_out


CACHE_HEAD_SCRIPT = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
from ray_shuffling_data_loader_tpu import runtime, ShufflingDataset
from ray_shuffling_data_loader_tpu.data_generation import generate_data

ctx = runtime.init_cluster(advertise_host="127.0.0.1", num_workers=2)
with open({addr_file!r} + ".tmp", "w") as f:
    f.write(ctx.cluster.address)
os.rename({addr_file!r} + ".tmp", {addr_file!r})
deadline = time.time() + 60
while len(ctx.cluster.registry.call("hosts")) < 2:
    if time.time() > deadline:
        print("VERDICT: FAIL worker never joined", flush=True)
        sys.exit(1)
    time.sleep(0.2)
filenames, _ = generate_data(
    num_rows=300000, num_files=6, num_row_groups_per_file=1,
    max_row_group_skew=0.0, data_dir={data_dir!r},
)
ds = ShufflingDataset(
    filenames, num_epochs=2, num_trainers=1, batch_size=50000, rank=0,
    num_reducers=4, seed=23, queue_name="ccd-test",
    narrow_to_32=True, cache_decoded=True,
)
ok = True
for epoch in range(2):
    ds.set_epoch(epoch)
    keys = sorted(k for b in ds for k in b["key"].tolist())
    if keys != list(range(300000)):
        ok = False
print("VERDICT: " + ("PASS" if ok else "FAIL"), flush=True)
runtime.shutdown()
"""


@slow
def test_cluster_decode_cache_exactly_once(tmp_path):
    """Two-host cluster with 32-bit narrowing AND the cross-epoch decode
    cache: later-epoch maps are locality-steered to the cache's owner and
    may fetch it over the (loopback) DCN — every row must still arrive
    exactly once per epoch."""
    addr_file = str(tmp_path / "head_address_cache")
    data_dir = str(tmp_path / "data_cache")
    env = dict(
        os.environ, RSDL_ADVERTISE_HOST="127.0.0.1", JAX_PLATFORMS="cpu"
    )
    head_log = tmp_path / "head_cache.log"
    worker_log = tmp_path / "worker_cache.log"
    with open(head_log, "w") as hf, open(worker_log, "w") as wf:
        head = subprocess.Popen(
            [sys.executable, "-c", CACHE_HEAD_SCRIPT.format(
                repo=_REPO, addr_file=addr_file, data_dir=data_dir
            )],
            stdout=hf, stderr=subprocess.STDOUT, env=env,
        )
        worker = subprocess.Popen(
            [sys.executable, "-c", WORKER_SCRIPT.format(
                repo=_REPO, addr_file=addr_file
            )],
            stdout=wf, stderr=subprocess.STDOUT, env=env,
        )
        try:
            head.wait(timeout=300)
        except subprocess.TimeoutExpired:
            pass
        finally:
            head.kill()
            worker.kill()
            head.wait()
            worker.wait()
    out = head_log.read_text()
    assert "VERDICT: PASS" in out, (
        f"head:\n{out}\n--- worker:\n{worker_log.read_text()}"
    )


PLACEMENT_HEAD_SCRIPT = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
from ray_shuffling_data_loader_tpu import runtime
from ray_shuffling_data_loader_tpu.runtime.cluster import PlacementProbe

ctx = runtime.init_cluster(advertise_host="127.0.0.1", num_workers=2)
with open({addr_file!r} + ".tmp", "w") as f:
    f.write(ctx.cluster.address)
os.rename({addr_file!r} + ".tmp", {addr_file!r})

deadline = time.time() + 60
while len(ctx.cluster.registry.call("hosts")) < 2:
    if time.time() > deadline:
        print("VERDICT: FAIL worker never joined", flush=True)
        sys.exit(1)
    time.sleep(0.2)

ok = True
hosts = runtime.cluster_hosts()
if len(hosts) != 2 or hosts[0] != ctx.cluster.host_id:
    ok = False
    print(f"VERDICT: FAIL cluster_hosts wrong: {{hosts}}", flush=True)
remote_id = hosts[1]

# Placement hint: the probe must land in the REMOTE host's session.
probe = runtime.spawn_actor(
    PlacementProbe, name="placed-probe", host_id=remote_id
)
info = probe.call("info")
if info["runtime_dir"] == ctx.runtime_dir:
    ok = False
    print("VERDICT: FAIL remote-placed actor ran in the head session",
          flush=True)

# host_id = own host spawns locally, same as no hint.
local = runtime.spawn_actor(PlacementProbe, host_id=ctx.cluster.host_id)
if local.call("info")["runtime_dir"] != ctx.runtime_dir:
    ok = False
    print("VERDICT: FAIL own-host placement left the head session",
          flush=True)

# The placed actor is cluster-discoverable by name.
if runtime.resolve_actor("placed-probe") is None:
    ok = False
    print("VERDICT: FAIL placed actor not in registry", flush=True)

# An unknown host id is a clear error, not a silent local spawn.
try:
    runtime.spawn_actor(PlacementProbe, host_id="no-such-host")
    ok = False
    print("VERDICT: FAIL unknown host_id accepted", flush=True)
except ValueError:
    pass

print("VERDICT: " + ("PASS" if ok else "FAIL"), flush=True)
runtime.shutdown()
"""


@slow
def test_actor_placement_on_host(tmp_path):
    """``spawn_actor(host_id=...)`` lands the actor in the target host's
    session via that host's agent — the SPREAD placement-group analog
    (reference ``benchmarks/benchmark.py:125-130``)."""
    addr_file = str(tmp_path / "head_address_place")
    env = dict(
        os.environ, RSDL_ADVERTISE_HOST="127.0.0.1", JAX_PLATFORMS="cpu"
    )
    head_log = tmp_path / "head_place.log"
    worker_log = tmp_path / "worker_place.log"
    with open(head_log, "w") as hf, open(worker_log, "w") as wf:
        head = subprocess.Popen(
            [sys.executable, "-c", PLACEMENT_HEAD_SCRIPT.format(
                repo=_REPO, addr_file=addr_file
            )],
            stdout=hf, stderr=subprocess.STDOUT, env=env,
        )
        worker = subprocess.Popen(
            [sys.executable, "-c", WORKER_SCRIPT.format(
                repo=_REPO, addr_file=addr_file
            )],
            stdout=wf, stderr=subprocess.STDOUT, env=env,
        )
        try:
            head.wait(timeout=240)
        except subprocess.TimeoutExpired:
            pass
        finally:
            head.kill()
            worker.kill()
            head.wait()
            worker.wait()
    out = head_log.read_text()
    assert "VERDICT: PASS" in out, (
        f"head:\n{out}\n--- worker:\n{worker_log.read_text()}"
    )


REJOIN_HEAD_SCRIPT = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
from ray_shuffling_data_loader_tpu import runtime, ShufflingDataset
from ray_shuffling_data_loader_tpu.data_generation import generate_data
from ray_shuffling_data_loader_tpu.runtime.actor import ActorHandle

ctx = runtime.init_cluster(advertise_host="127.0.0.1", num_workers=2)
with open({addr_file!r} + ".tmp", "w") as f:
    f.write(ctx.cluster.address)
os.rename({addr_file!r} + ".tmp", {addr_file!r})

deadline = time.time() + 60
while len(ctx.cluster.registry.call("hosts")) < 2:
    if time.time() > deadline:
        print("VERDICT: FAIL worker never joined", flush=True)
        sys.exit(1)
    time.sleep(0.2)
# Signal the test to SIGKILL the worker and start a replacement.
open({joined_file!r}, "w").close()
while os.path.exists({joined_file!r}):
    time.sleep(0.1)

filenames, _ = generate_data(
    num_rows=1500, num_files=3, num_row_groups_per_file=1,
    max_row_group_skew=0.0, data_dir={data_dir!r},
)
ok = True

# Trial part 1, with the dead host still in the membership table: the
# scheduler must evict it mid-trial and the epoch must stay exactly-once.
ds = ShufflingDataset(
    filenames, num_epochs=1, num_trainers=1, batch_size=250, rank=0,
    num_reducers=3, seed=19, queue_name="q-rejoin-1",
)
ds.set_epoch(0)
keys = sorted(k for b in ds for k in b["key"].tolist())
if keys != list(range(1500)):
    ok = False
    print("VERDICT: FAIL epoch with dead host not exactly-once", flush=True)

# The replacement host joins (membership heartbeat); wait until a second
# LIVE agent is registered again.
deadline = time.time() + 120
def live_agents():
    hosts = ctx.cluster.registry.call("hosts")
    return {{
        hid: info for hid, info in hosts.items()
        if ActorHandle(tuple(info["agent"])).ping(timeout=2.0)
    }}
while len(live_agents()) < 2:
    if time.time() > deadline:
        print("VERDICT: FAIL replacement host never joined", flush=True)
        print("VERDICT: FAIL", flush=True)
        runtime.shutdown()
        sys.exit(1)
    time.sleep(0.5)
ctx.cluster.refresh_scheduler()

# Trial part 2: the rejoined host must RECEIVE WORK and the epoch must
# stay exactly-once.
before = {{
    hid: ActorHandle(tuple(info["agent"])).call("agent_stats")["completed"]
    for hid, info in live_agents().items()
    if hid != ctx.cluster.host_id
}}
ds2 = ShufflingDataset(
    filenames, num_epochs=1, num_trainers=1, batch_size=250, rank=0,
    num_reducers=3, seed=23, queue_name="q-rejoin-2",
)
ds2.set_epoch(0)
keys = sorted(k for b in ds2 for k in b["key"].tolist())
if keys != list(range(1500)):
    ok = False
    print("VERDICT: FAIL post-rejoin epoch not exactly-once", flush=True)
after = {{
    hid: ActorHandle(tuple(info["agent"])).call("agent_stats")["completed"]
    for hid in before
    for info in [ctx.cluster.registry.call("hosts")[hid]]
}}
gained = {{hid: after[hid] - before.get(hid, 0) for hid in after}}
print(f"rejoined-host task gain: {{gained}}", flush=True)
if not gained or not all(g > 0 for g in gained.values()):
    ok = False
    print("VERDICT: FAIL rejoined host received no work", flush=True)

print("VERDICT: " + ("PASS" if ok else "FAIL"), flush=True)
runtime.shutdown()
"""


@slow
def test_host_rejoin_reworks(tmp_path):
    """A host that dies mid-trial and is replaced by a rejoining one must
    be evicted, then re-admitted via the membership heartbeat, and must
    receive new tasks — with both epochs exactly-once (VERDICT r3 item 6;
    the reference has no elasticity at all, SURVEY §5)."""
    addr_file = str(tmp_path / "head_address_rejoin")
    joined_file = str(tmp_path / "worker_joined_rejoin")
    data_dir = str(tmp_path / "data_rejoin")
    env = dict(
        os.environ, RSDL_ADVERTISE_HOST="127.0.0.1", JAX_PLATFORMS="cpu"
    )
    head_log = tmp_path / "head_rejoin.log"
    w1_log = tmp_path / "worker1_rejoin.log"
    w2_log = tmp_path / "worker2_rejoin.log"
    with open(head_log, "w") as hf, open(w1_log, "w") as w1f, \
            open(w2_log, "w") as w2f:
        head = subprocess.Popen(
            [sys.executable, "-c", REJOIN_HEAD_SCRIPT.format(
                repo=_REPO, addr_file=addr_file, joined_file=joined_file,
                data_dir=data_dir,
            )],
            stdout=hf, stderr=subprocess.STDOUT, env=env,
        )
        worker1 = subprocess.Popen(
            [sys.executable, "-c", WORKER_SCRIPT.format(
                repo=_REPO, addr_file=addr_file
            )],
            stdout=w1f, stderr=subprocess.STDOUT, env=env,
        )
        worker2 = None
        try:
            deadline = time.time() + 120
            while not os.path.exists(joined_file):
                assert time.time() < deadline, "worker never joined"
                assert head.poll() is None, "head died early"
                time.sleep(0.2)
            worker1.kill()
            worker1.wait()
            worker2 = subprocess.Popen(
                [sys.executable, "-c", WORKER_SCRIPT.format(
                    repo=_REPO, addr_file=addr_file
                )],
                stdout=w2f, stderr=subprocess.STDOUT, env=env,
            )
            os.unlink(joined_file)
            head.wait(timeout=300)
        except subprocess.TimeoutExpired:
            pass
        finally:
            head.kill()
            worker1.kill()
            if worker2 is not None:
                worker2.kill()
            head.wait()
            worker1.wait()
            if worker2 is not None:
                worker2.wait()
    out = head_log.read_text()
    assert "VERDICT: PASS" in out, (
        f"head:\n{out}\n--- worker1:\n{w1_log.read_text()}"
        f"\n--- worker2:\n{w2_log.read_text()}"
    )
