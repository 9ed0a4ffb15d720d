"""Warm-start subsystem: compilation-cache service + warm session pools.

Drives odh_kubeflow_tpu/warmup end-to-end against the embedded
apiserver + kubelet sim:

- the compile cache's contract — content-addressed hit/miss,
  singleflight (N concurrent compilers, ONE compile), digest-verified
  loads (a corrupted artifact is detected and recompiled, never handed
  to XLA), TTL + LRU retention, the jax persistent-cache bridge
  (ingest/materialize), zone-replicated artifacts that survive a zone
  loss and heal, and index entries that survive WAL leader failover;
- the warm pool's contract — backfill to spec.size through the slice
  queue at the negative backfill priority, atomic claim (a concurrent
  spawn race hands out exactly one standby; a WAL kill-point sweep
  over the claim write proves crash recovery cannot double-hand-out),
  claimed-standby reap + backfill, zone-kill drain + re-backfill in
  the surviving zone, and the JWA spawn path's warm handout with the
  template kernel state restored through the ordinary resume
  machinery.
"""

import os
import threading
import time

import pytest

from odh_kubeflow_tpu.apis import (
    TPU_ACCELERATOR_ANNOTATION,
    TPU_TOPOLOGY_ANNOTATION,
    register_crds,
)
from odh_kubeflow_tpu.controllers.notebook import (
    NotebookController,
    NotebookControllerConfig,
)
from odh_kubeflow_tpu.controllers.runtime import Manager
from odh_kubeflow_tpu.machinery import objects as obj_util
from odh_kubeflow_tpu.machinery.faults import (
    CrashPoint,
    KillPointIO,
    chaos_seed,
)
from odh_kubeflow_tpu.machinery.kubelet import FakeCluster
from odh_kubeflow_tpu.machinery.store import APIServer, NotFound
from odh_kubeflow_tpu.machinery.wal import WriteAheadLog
from odh_kubeflow_tpu.scheduling import register_scheduling
from odh_kubeflow_tpu.scheduling.scheduler import SliceScheduler
from odh_kubeflow_tpu.sessions import register_sessions
from odh_kubeflow_tpu.sessions.checkpoint import SessionCheckpointStore
from odh_kubeflow_tpu.sessions.manager import SessionConfig, SessionManager
from odh_kubeflow_tpu.utils.prometheus import Registry, lint_metric_names
from odh_kubeflow_tpu.warmup import (
    POOL_LABEL,
    STANDBY_ANNOTATION,
    WARM_FROM_ANNOTATION,
    is_claimed,
    register_warmup,
)
from odh_kubeflow_tpu.warmup.compilecache import (
    CompileArtifactStore,
    CompileCacheConfig,
    CompileCacheService,
    CompileKey,
    ReplicatedArtifactStore,
    install_process_cache,
)
from odh_kubeflow_tpu.warmup.pool import (
    WarmPoolConfig,
    WarmPoolController,
    claim_standby,
    new_warm_pool,
)

V5E = "tpu-v5-lite-podslice"
SEED = chaos_seed() or 20260806


# ---------------------------------------------------------------------------
# compile cache — service harness


def cache_service(tmp_path, api=None, zones="", registry=None, **cfg):
    api = api or _warmup_api()
    return (
        CompileCacheService(
            api,
            CompileCacheConfig(
                cache_dir=str(tmp_path / "cc"), zones=zones, **cfg
            ),
            registry=registry or Registry(),
        ),
        api,
    )


def _warmup_api():
    api = APIServer()
    register_warmup(api)
    return api


def test_compile_cache_miss_then_hit(tmp_path):
    reg = Registry()
    svc, api = cache_service(tmp_path, registry=reg)
    key = CompileKey("prog-a", topology="2x2", compiler_version="jax-t")
    calls = []

    def compile_fn():
        calls.append(1)
        return b"xla-artifact-bytes"

    assert svc.get_or_compile(key, compile_fn) == b"xla-artifact-bytes"
    assert svc.get_or_compile(key, compile_fn) == b"xla-artifact-bytes"
    assert len(calls) == 1, "second call must be a cache hit"
    assert svc.m_hits.value() == 1
    assert svc.m_misses.value({"reason": "cold"}) == 1
    entry = api.get("CompileCacheEntry", key.entry_name)
    status = entry["status"]
    assert status["digest"] == CompileArtifactStore.digest_of(
        b"xla-artifact-bytes"
    )
    assert status["sizeBytes"] == len(b"xla-artifact-bytes")
    lint_metric_names(reg)


def test_singleflight_dedups_concurrent_compiles(tmp_path):
    svc, _ = cache_service(tmp_path)
    key = CompileKey("prog-sf", topology="2x2")
    compiles = []
    gate = threading.Event()

    def compile_fn():
        compiles.append(1)
        gate.wait(2.0)  # hold the leader so followers pile up
        return b"one-artifact"

    results: list[bytes] = []

    def worker():
        results.append(svc.get_or_compile(key, compile_fn))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    # wait for followers to park on the in-flight leader, then release
    deadline = time.monotonic() + 2.0
    while svc.m_waits.value() < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    gate.set()
    for t in threads:
        t.join(timeout=5)
    assert len(compiles) == 1, "singleflight must compile exactly once"
    assert results == [b"one-artifact"] * 8
    assert svc.m_waits.value() >= 1


def test_corrupt_artifact_detected_and_recompiled(tmp_path):
    svc, api = cache_service(tmp_path)
    key = CompileKey("prog-c", topology="2x2")
    svc.put(key, b"good-bytes")
    # flip the stored bytes under the index's digest
    with open(os.path.join(svc.root, f"{key.key_id}.bin"), "wb") as f:
        f.write(b"bitrot!!")
    assert svc.load(key) is None, "corrupt bytes must never load"
    # the lying index entry was purged with the bytes
    with pytest.raises(NotFound):
        api.get("CompileCacheEntry", key.entry_name)
    calls = []
    got = svc.get_or_compile(key, lambda: calls.append(1) or b"fresh")
    assert got == b"fresh" and calls == [1]
    assert svc.load(key) == b"fresh"


def test_gc_ttl_and_lru(tmp_path):
    svc, api = cache_service(tmp_path, ttl_seconds=10.0, max_bytes=0)
    old = CompileKey("prog-old")
    fresh = CompileKey("prog-fresh")
    svc.put(old, b"o" * 8)
    svc.put(fresh, b"f" * 8)
    _stamp_access(api, old, "2020-01-01T00:00:00Z")
    assert svc.gc() == 1  # the stale entry TTL-expires
    assert svc.load(old) is None
    assert svc.load(fresh) == b"f" * 8
    assert svc.m_evictions.value({"reason": "ttl"}) == 1

    # LRU: ttl off, byte budget forces out the least recently used
    svc2, api2 = cache_service(
        tmp_path / "lru", ttl_seconds=0.0, max_bytes=20
    )
    keys = [CompileKey(f"prog-{i}") for i in range(3)]
    for i, k in enumerate(keys):
        svc2.put(k, bytes([65 + i]) * 10)  # 30 bytes total, budget 20
        _stamp_access(api2, k, f"2026-01-01T00:00:0{i}Z")
    svc2.gc()
    assert svc2.load(keys[0]) is None, "oldest access must evict first"
    assert svc2.load(keys[1]) is not None
    assert svc2.load(keys[2]) is not None
    assert svc2.m_bytes.value() == 20


def _stamp_access(api, key, ts):
    entry = obj_util.mutable(api.get("CompileCacheEntry", key.entry_name))
    entry["status"]["lastAccessAt"] = ts
    entry["status"]["createdAt"] = ts
    api.update_status(entry)


def test_replicated_store_zone_loss_and_heal(tmp_path):
    za, zb = str(tmp_path / "za"), str(tmp_path / "zb")
    svc, api = cache_service(tmp_path, zones=f"za={za},zb={zb}")
    assert isinstance(svc.store, ReplicatedArtifactStore)
    key = CompileKey("prog-z", topology="2x2")
    svc.put(key, b"replicated-bytes")
    entry = api.get("CompileCacheEntry", key.entry_name)
    assert sorted(entry["status"]["zones"]) == ["za", "zb"]
    assert not entry["status"]["replicationDegraded"]

    # one zone dark: loads still verify from the survivor
    svc.store.fail_zone("za")
    assert svc.load(key) == b"replicated-bytes"

    # a put while degraded lands on the survivor and says so ...
    key2 = CompileKey("prog-z2", topology="2x2")
    svc.put(key2, b"degraded-write")
    entry2 = api.get("CompileCacheEntry", key2.entry_name)
    assert entry2["status"]["zones"] == ["zb"]
    assert entry2["status"]["replicationDegraded"]
    # ... and the heal pass re-replicates once the zone returns
    svc.store.heal_zone("za")
    assert svc.heal_pass() == 1
    entry2 = api.get("CompileCacheEntry", key2.entry_name)
    assert sorted(entry2["status"]["zones"]) == ["za", "zb"]
    assert not entry2["status"]["replicationDegraded"]
    assert (
        CompileArtifactStore(za).load(key2.key_id)[0] == b"degraded-write"
    )

    # zone bitrot (not outage): the bad replica falls through to the
    # verifying one
    with open(os.path.join(zb, f"{key.key_id}.bin"), "wb") as f:
        f.write(b"garbage")
    assert svc.load(key) == b"replicated-bytes"


def test_cache_entries_survive_wal_failover(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog(d)
    api = APIServer.recover(wal)
    register_warmup(api)
    cfg = CompileCacheConfig(
        cache_dir=str(tmp_path / "cc"),
        zones=f"za={tmp_path / 'za'},zb={tmp_path / 'zb'}",
    )
    svc = CompileCacheService(api, cfg, registry=Registry())
    key = CompileKey("prog-f", topology="2x2", compiler_version="v")
    svc.get_or_compile(key, lambda: b"survives-failover")
    wal.close()

    # the new leader recovers the index from the WAL and serves the
    # artifact from the replicated store — no recompile
    rec = APIServer.recover(WriteAheadLog(d))
    svc2 = CompileCacheService(rec, cfg, registry=Registry())

    def must_not_compile():
        raise AssertionError("failover must not force a recompile")

    assert svc2.get_or_compile(key, must_not_compile) == b"survives-failover"
    assert svc2.stats()["entries"] == 1


def test_ingest_and_materialize_bridge_jax_cache_dirs(tmp_path):
    svc, _ = cache_service(tmp_path)
    # the caller names the directory (a process's cache directory is
    # placed by the deployment, not handed out by the service)
    staging = str(tmp_path / "cold-run")
    os.makedirs(staging)
    for name, data in (("fp-aaa", b"prog a"), ("fp-bbb", b"prog b")):
        with open(os.path.join(staging, name), "wb") as f:
            f.write(data)
    assert svc.ingest_dir(staging, topology="2x2", compiler_ver="v1") == 2
    # re-ingest of bit-identical artifacts is a no-op
    assert svc.ingest_dir(staging, topology="2x2", compiler_ver="v1") == 0

    warm = str(tmp_path / "warm")
    assert svc.materialize_dir(warm, topology="2x2", compiler_ver="v1") == 2
    assert open(os.path.join(warm, "fp-aaa"), "rb").read() == b"prog a"
    assert open(os.path.join(warm, "fp-bbb"), "rb").read() == b"prog b"
    # other topologies/compilers stage nothing
    assert (
        svc.materialize_dir(str(tmp_path / "w2"), topology="4x4",
                            compiler_ver="v1")
        == 0
    )


def test_install_process_cache_one_rule(tmp_path, monkeypatch):
    """The directory rule: JAX_COMPILATION_CACHE_DIR set → that
    directory and no other, and the code sets none (jax read the
    variable itself); unset → the fixed, git-ignored directory inside
    the checkout. No keyword beats the variable, nothing comes from
    tempfile."""
    import inspect
    import subprocess

    import jax

    from odh_kubeflow_tpu.warmup import compilecache

    assert not inspect.signature(install_process_cache).parameters
    from odh_kubeflow_tpu.models.engine import DecodeEngine

    assert "compile_cache_dir" not in inspect.signature(
        DecodeEngine.__init__
    ).parameters

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".jax_compile_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        # unset → the fixed in-checkout path, the same on every call
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compilecache.process_cache_dir() == fixed
        assert install_process_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert os.path.isdir(fixed)
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", fixed], cwd=repo
        )
        assert ignored.returncode in (0, 128)  # 128: not a git checkout
        # set after jax was imported, jax has NOT read it: the process
        # would cache somewhere nobody placed — an error, not a guess
        target = str(tmp_path / "jaxcc")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        assert compilecache.process_cache_dir() == target
        with pytest.raises(RuntimeError, match="before jax is imported"):
            install_process_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

    # set from outside before the process started — jax read it at
    # import (simulated: both hold the same value): that directory is
    # returned and the code sets none
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
    jax.config.update("jax_compilation_cache_dir", target)
    seen = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: (seen.append(k), real_update(k, v))
    )
    try:
        assert install_process_cache() == target
        assert "jax_compilation_cache_dir" not in seen
        assert jax.config.jax_compilation_cache_dir == target
    finally:
        real_update("jax_compilation_cache_dir", before)

    # no tempfile on the path from a process to its cache directory
    for fn in (install_process_cache, compilecache.process_cache_dir):
        assert "tempfile" not in inspect.getsource(fn)
    assert not hasattr(CompileCacheService, "staging_dir")


# ---------------------------------------------------------------------------
# warm pools — platform harness


def make_env(
    tmp_path,
    pools=1,
    zones=None,
    grace=60.0,
    compile_cache_mount="",
):
    api = APIServer()
    register_crds(api)
    register_scheduling(api)
    register_sessions(api)
    register_warmup(api)
    cluster = FakeCluster(api)
    registry = Registry()
    mgr = Manager(api)
    store = SessionCheckpointStore(str(tmp_path / "ckpts"), backend="json")
    session_mgr = SessionManager(
        api,
        SessionConfig(checkpoint_dir=str(tmp_path / "ckpts"), backend="json"),
        registry=registry,
        runtime=cluster.session_runtime,
        store=store,
    )
    ctrl = NotebookController(
        api=api,
        config=NotebookControllerConfig(
            enable_queueing=True,
            enable_sessions=True,
            compile_cache_mount=compile_cache_mount,
        ),
        registry=registry,
    )
    ctrl.register(mgr)
    session_mgr.register(mgr)
    scheduler = SliceScheduler(api, registry=registry, suspender=session_mgr)
    scheduler.register(mgr)
    cc = CompileCacheService(
        api,
        CompileCacheConfig(cache_dir=str(tmp_path / "cc")),
        registry=registry,
    )
    warm = WarmPoolController(
        api,
        WarmPoolConfig(claim_grace_seconds=grace, resync_seconds=0.05),
        registry=registry,
        session_store=store,
        compile_cache=cc,
    )
    warm.register(mgr)
    if zones:
        for zone, count in zones.items():
            for i in range(count):
                cluster.add_tpu_node_pool(
                    f"{zone}-pool-{i}", V5E, "2x2",
                    num_hosts=1, chips_per_host=4, zone=zone,
                )
    else:
        for i in range(pools):
            cluster.add_tpu_node_pool(
                f"pool-{i}", V5E, "2x2", num_hosts=1, chips_per_host=4
            )
    return api, cluster, mgr, registry, session_mgr, warm, cc, store


def quiesce(cluster, mgr, rounds=6):
    for _ in range(rounds):
        cluster.step()
        mgr.drain()
        time.sleep(0.002)


def converge(cluster, mgr, warm, pred, rounds=60):
    for _ in range(rounds):
        if pred():
            return True
        cluster.step()
        mgr.drain()
        # the resync tick (normally requeue_after-driven) by hand, so
        # tests never wait on wall-clock timers
        for pool in cluster.api.list("WarmPool"):
            from odh_kubeflow_tpu.controllers.runtime import Request

            warm.reconcile(
                Request(obj_util.namespace_of(pool), obj_util.name_of(pool))
            )
        time.sleep(0.005)
    return pred()


def pool_status(api, name="wp", ns="team-a"):
    return api.get("WarmPool", name, ns).get("status") or {}


def test_warm_pool_backfills_to_size_at_backfill_priority(tmp_path):
    api, cluster, mgr, registry, _, warm, _, _ = make_env(tmp_path, pools=2)
    api.create(
        new_warm_pool(
            "wp", "team-a", size=2, accelerator=V5E, topology="2x2",
            image="jax:latest",
        )
    )
    assert converge(
        cluster, mgr, warm,
        lambda: pool_status(api).get("readyStandbys") == 2,
    ), f"pool never ready: {pool_status(api)}"

    names = set()
    for nb in api.list("Notebook", namespace="team-a"):
        assert obj_util.labels_of(nb).get(POOL_LABEL) == "wp"
        assert obj_util.annotations_of(nb).get(STANDBY_ANNOTATION) == "true"
        names.add(obj_util.name_of(nb))
        # the standby's gang rode the queue at the backfill priority:
        # behind every real user, first victim under pressure
        wl = api.get("Workload", obj_util.name_of(nb), "team-a")
        assert wl["spec"]["priority"] == -100
        assert wl["spec"]["priorityClassName"] == "warm-pool-backfill"
    assert names == {"wp-standby-0", "wp-standby-1"}
    assert api.get("PriorityClass", "warm-pool-backfill")["value"] == -100
    assert warm.m_ready.value({"pool": "wp"}) == 2

    # scale down: spec.size 2 → 1 reaps the surplus standby
    pool = obj_util.mutable(api.get("WarmPool", "wp", "team-a"))
    pool["spec"]["size"] = 1
    api.update(pool)
    assert converge(
        cluster, mgr, warm,
        lambda: len(list(api.list("Notebook", namespace="team-a"))) == 1,
    )
    lint_metric_names(registry)


def test_concurrent_claims_hand_out_exactly_one_standby(tmp_path):
    api, cluster, mgr, _, _, warm, _, _ = make_env(tmp_path, pools=1)
    api.create(
        new_warm_pool(
            "wp", "team-a", size=1, accelerator=V5E, topology="2x2",
            image="jax:latest",
        )
    )
    assert converge(
        cluster, mgr, warm,
        lambda: pool_status(api).get("readyStandbys") == 1,
    )

    results = []
    barrier = threading.Barrier(8)

    def racer(i):
        barrier.wait()
        results.append(
            claim_standby(
                api, "team-a", accelerator=V5E, claimant=f"spawner-{i}"
            )
        )

    threads = [
        threading.Thread(target=racer, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=5)
    wins = [r for r in results if r is not None]
    assert len(results) == 8
    assert len(wins) == 1, f"exactly one spawner may win, got {len(wins)}"
    assert wins[0]["pool"] == "wp" and wins[0]["standby"] == "wp-standby-0"
    assert is_claimed(api.get("Notebook", "wp-standby-0", "team-a"))
    # a late spawner finds nothing — no double handout
    assert claim_standby(api, "team-a", accelerator=V5E) is None


def test_claimed_standby_reaped_after_grace_and_backfilled(tmp_path):
    api, cluster, mgr, _, _, warm, _, _ = make_env(
        tmp_path, pools=2, grace=0.0
    )
    api.create(
        new_warm_pool(
            "wp", "team-a", size=1, accelerator=V5E, topology="2x2",
            image="jax:latest",
        )
    )
    assert converge(
        cluster, mgr, warm,
        lambda: pool_status(api).get("readyStandbys") == 1,
    )
    got = claim_standby(api, "team-a", accelerator=V5E, claimant="crashed")
    assert got is not None
    # the claimant died before deleting its standby: with the grace
    # window elapsed the controller reaps it and backfills a fresh one
    assert converge(
        cluster, mgr, warm,
        lambda: (
            pool_status(api).get("readyStandbys") == 1
            and not any(
                is_claimed(nb)
                for nb in api.list("Notebook", namespace="team-a")
            )
        ),
    ), "claimed standby never reaped + backfilled"
    assert warm.m_reaps.value({"reason": "claimed"}) >= 1


# ---------------------------------------------------------------------------
# claim durability — WAL kill-point sweep


def _claim_wal_env(d, io=None):
    wal = WriteAheadLog(d, io=io) if io is not None else WriteAheadLog(d)
    api = APIServer.recover(wal)
    register_crds(api)
    register_warmup(api)
    return api


def _seed_claim_state(api):
    api.create(
        new_warm_pool(
            "wp", "team-a", size=1, accelerator=V5E, topology="2x2",
            image="jax:latest",
        )
    )
    api.create(
        {
            "apiVersion": "kubeflow.org/v1beta1",
            "kind": "Notebook",
            "metadata": {
                "name": "wp-standby-0",
                "namespace": "team-a",
                "labels": {POOL_LABEL: "wp"},
                "annotations": {
                    STANDBY_ANNOTATION: "true",
                    TPU_ACCELERATOR_ANNOTATION: V5E,
                    TPU_TOPOLOGY_ANNOTATION: "2x2",
                },
            },
            "spec": {
                "template": {
                    "spec": {
                        "containers": [{"name": "nb", "image": "jax:latest"}]
                    }
                }
            },
        }
    )
    pod = api.create(
        {
            "apiVersion": "v1",
            "kind": "Pod",
            "metadata": {"name": "wp-standby-0-0", "namespace": "team-a"},
            "spec": {"containers": []},
        }
    )
    pod = obj_util.mutable(pod)
    pod["status"] = {"phase": "Running"}
    api.update_status(pod)


@pytest.mark.parametrize("after_op", [False, True])
def test_claim_kill_point_sweep_no_double_handout(tmp_path, after_op):
    """Process death injected at every WAL IO op of the claim write
    (mid-append, pre-fsync, post-fsync pre-ack): after recovery the
    standby is handed out AT MOST once in total, and a claim that
    reached the WAL is honored — the recovered control plane never
    hands that standby to a second spawner."""
    probe_io = KillPointIO(10**9, seed=SEED)
    api = _claim_wal_env(str(tmp_path / "probe"), io=probe_io)
    _seed_claim_state(api)
    setup_ops = probe_io.ops
    assert (
        claim_standby(api, "team-a", accelerator=V5E, claimant="probe")
        is not None
    )
    total_ops = probe_io.ops
    assert total_ops > setup_ops, "the claim must be WAL IO"

    for kill_at in range(setup_ops + 1, total_ops + 1):
        d = str(tmp_path / f"k{int(after_op)}-{kill_at}")
        io = KillPointIO(
            kill_at, seed=SEED * 1000 + kill_at, after_op=after_op
        )
        api = _claim_wal_env(d, io=io)
        _seed_claim_state(api)
        delivered = 0
        try:
            if (
                claim_standby(
                    api, "team-a", accelerator=V5E, claimant="victim"
                )
                is not None
            ):
                delivered += 1
        except CrashPoint:
            pass
        assert io.dead, f"kill@{kill_at}: the crash must fire mid-claim"

        rec = _recover(d)
        recovered_claimed = is_claimed(
            rec.get("Notebook", "wp-standby-0", "team-a")
        )
        got = claim_standby(
            rec, "team-a", accelerator=V5E, claimant="post-recovery"
        )
        if got is not None:
            delivered += 1
        assert delivered <= 1, f"kill@{kill_at}: double handout"
        if recovered_claimed:
            # the crashed claim reached the WAL: recovery must honor it
            assert got is None, (
                f"kill@{kill_at}: durable claim handed out again"
            )
        # either way the standby ends claimed and is never served again
        assert is_claimed(rec.get("Notebook", "wp-standby-0", "team-a"))
        assert (
            claim_standby(rec, "team-a", accelerator=V5E) is None
        ), f"kill@{kill_at}: third spawner got the claimed standby"


def _recover(d, attempts=3):
    last: Exception = RuntimeError("unreachable")
    for _ in range(attempts):
        try:
            return APIServer.recover(WriteAheadLog(d))
        except Exception as e:  # pragma: no cover - torn-tail retry
            last = e
    raise last


# ---------------------------------------------------------------------------
# zone kill → drain + backfill


def test_zone_kill_drains_standbys_and_backfills_surviving_zone(tmp_path):
    api, cluster, mgr, _, _, warm, _, _ = make_env(
        tmp_path, zones={"zone-a": 2, "zone-b": 2}
    )
    api.create(
        new_warm_pool(
            "wp", "team-a", size=2, accelerator=V5E, topology="2x2",
            image="jax:latest",
        )
    )
    assert converge(
        cluster, mgr, warm,
        lambda: pool_status(api).get("readyStandbys") == 2,
        rounds=80,
    )

    killed = cluster.kill_zone("zone-a")
    assert killed, "drill must actually kill nodes"
    # dead standbys are not claimable mid-drill — a claim either finds
    # a live one or nothing, never a corpse
    got = claim_standby(api, "team-a", accelerator=V5E, claimant="mid-kill")
    if got is not None:
        pod = api.get("Pod", f"{got['standby']}-0", "team-a")
        assert pod["status"]["phase"] == "Running"
        api.delete("Notebook", got["standby"], "team-a")

    def healthy_in_survivor():
        status = pool_status(api)
        if status.get("readyStandbys") != 2:
            return False
        return status.get("zones") == ["zone-b"]

    assert converge(
        cluster, mgr, warm, healthy_in_survivor, rounds=120
    ), f"pool never re-backfilled in the survivor: {pool_status(api)}"


# ---------------------------------------------------------------------------
# JWA warm handout e2e


def _jwa(api, registry):
    from odh_kubeflow_tpu.web.jwa import JupyterWebApp

    return JupyterWebApp(api, registry=registry)


def _spawn_body(name, image="jax:latest"):
    return {
        "name": name,
        "image": image,
        "cpu": "1",
        "memory": "2Gi",
        "workspaceVolume": None,
        "dataVolumes": [],
        "tpus": {"accelerator": V5E, "topology": "2x2"},
    }


def test_jwa_spawn_claims_standby_and_restores_template_state(tmp_path):
    api, cluster, mgr, registry, _, warm, _, store = make_env(
        tmp_path, pools=1
    )
    jwa = _jwa(api, registry)
    api.create(
        new_warm_pool(
            "wp", "team-a", size=1, accelerator=V5E, topology="2x2",
            image="jax:latest",
        )
    )
    assert converge(
        cluster, mgr, warm,
        lambda: pool_status(api).get("readyStandbys") == 1,
    )
    standby_wl = api.get("Workload", "wp-standby-0", "team-a")
    freed_pool = standby_wl["status"]["assignment"]["pool"]

    resp = jwa.create_notebook("team-a", _spawn_body("warm-nb"), "u")
    assert resp.status == 201, resp.body
    nb = api.get("Notebook", "warm-nb", "team-a")
    ann = obj_util.annotations_of(nb)
    assert ann[WARM_FROM_ANNOTATION] == "wp"
    # the standby was consumed — its slice is free for the claimant
    with pytest.raises(NotFound):
        api.get("Notebook", "wp-standby-0", "team-a")

    def restored():
        try:
            ckpt = api.get("SessionCheckpoint", "warm-nb", "team-a")
        except NotFound:
            return False
        return (
            obj_util.get_path(ckpt, "status", "phase", default="")
            == "Restored"
        )

    assert converge(cluster, mgr, warm, restored, rounds=80), (
        "warm template state never restored into the claimed notebook"
    )
    # the claimed gang landed exactly where the standby freed capacity
    wl = api.get("Workload", "warm-nb", "team-a")
    assert wl["spec"]["preferredPool"] == freed_pool
    assert wl["status"]["assignment"]["pool"] == freed_pool
    # the restored kernel holds the pool's pre-warmed template state
    state = cluster.get_session_state("team-a", "warm-nb")
    assert state and state.get("warmpool") == "wp"
    assert state.get("preheated") is True

    # the details feed explains the warm handout
    details = jwa._warm_row(api.get("Notebook", "warm-nb", "team-a"))
    assert details == {
        "pool": "wp",
        "standby": "wp-standby-0",
        "claimedAt": ann["warmup.kubeflow.org/claimed-at"],
        "restored": True,
    }


def test_jwa_spawn_cold_path_when_no_pool_matches(tmp_path):
    api, cluster, mgr, registry, _, warm, _, _ = make_env(tmp_path, pools=2)
    jwa = _jwa(api, registry)
    api.create(
        new_warm_pool(
            "wp", "team-a", size=1, accelerator=V5E, topology="2x2",
            image="jax:latest",
        )
    )
    assert converge(
        cluster, mgr, warm,
        lambda: pool_status(api).get("readyStandbys") == 1,
    )
    # different image → template mismatch → ordinary cold spawn
    resp = jwa.create_notebook(
        "team-a", _spawn_body("cold-nb", image="other:latest"), "u"
    )
    assert resp.status == 201, resp.body
    nb = api.get("Notebook", "cold-nb", "team-a")
    assert WARM_FROM_ANNOTATION not in obj_util.annotations_of(nb)
    # the standby is untouched
    assert not is_claimed(api.get("Notebook", "wp-standby-0", "team-a"))
    assert jwa._warm_row(nb) is None


# ---------------------------------------------------------------------------
# kubelet image-pull sim + compile-cache mount


def test_sim_image_pull_gates_cold_start_and_warm_node_skips_it(tmp_path):
    api, cluster, mgr, registry, _, warm, _, _ = make_env(tmp_path, pools=1)
    cluster.image_pull_seconds = 0.15
    jwa = _jwa(api, registry)
    assert jwa.create_notebook("team-a", _spawn_body("cold-nb"), "u").status == 201

    def pod_phase():
        try:
            return api.get("Pod", "cold-nb-0", "team-a")["status"]["phase"]
        except (NotFound, KeyError):
            return ""

    saw_pulling = False
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        quiesce(cluster, mgr, rounds=1)
        phase = pod_phase()
        if phase == "Pending":
            pod = api.get("Pod", "cold-nb-0", "team-a")
            msgs = [
                c.get("message", "")
                for c in pod["status"].get("conditions", [])
            ]
            if any("pulling image" in m for m in msgs):
                saw_pulling = True
        if phase == "Running":
            break
        time.sleep(0.02)
    assert pod_phase() == "Running"
    assert saw_pulling, "cold start must pass through the image pull"
    node = api.get("Pod", "cold-nb-0", "team-a")["spec"]["nodeName"]
    assert "jax:latest" in cluster.node_images(node)

    # same image on the now-warm node: no pull round
    api.delete("Notebook", "cold-nb", "team-a")
    quiesce(cluster, mgr, rounds=4)
    assert jwa.create_notebook("team-a", _spawn_body("warm2-nb"), "u").status == 201
    saw_pulling = False
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        quiesce(cluster, mgr, rounds=1)
        try:
            pod = api.get("Pod", "warm2-nb-0", "team-a")
        except NotFound:
            continue
        msgs = [
            c.get("message", "")
            for c in pod["status"].get("conditions", [])
        ]
        if any("pulling image" in m for m in msgs):
            saw_pulling = True
        if pod["status"].get("phase") == "Running":
            break
        time.sleep(0.02)
    assert not saw_pulling, "warm node must not re-pull a held image"


def test_compile_cache_mount_lands_in_statefulset_env(tmp_path):
    api, cluster, mgr, _, _, _, _, _ = make_env(
        tmp_path, pools=1, compile_cache_mount="/cache/xla"
    )
    api.create(
        {
            "apiVersion": "kubeflow.org/v1beta1",
            "kind": "Notebook",
            "metadata": {
                "name": "nb",
                "namespace": "team-a",
                "annotations": {
                    TPU_ACCELERATOR_ANNOTATION: V5E,
                    TPU_TOPOLOGY_ANNOTATION: "2x2",
                },
            },
            "spec": {
                "template": {
                    "spec": {
                        "containers": [{"name": "nb", "image": "jax:latest"}]
                    }
                }
            },
        }
    )
    quiesce(cluster, mgr, rounds=4)
    sts = api.get("StatefulSet", "nb", "team-a")
    env = {
        e["name"]: e.get("value", "")
        for e in sts["spec"]["template"]["spec"]["containers"][0]["env"]
    }
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/cache/xla"
