"""Suspend-to-checkpoint sessions + chip oversubscription.

Drives the sessions/ subsystem end-to-end against the embedded
apiserver + kubelet sim (whose checkpoint/restore container hooks hold
"container memory" that dies with the pod): suspend on cull with the
distinct Suspended event, the scale-down held until the snapshot is
durable, the Workload deletion that frees the slice reservation, warm
resume with bit-identical state restored before ready, the scheduler's
checkpoint-then-preempt (suspendable victims before hard kills,
``workload_preemptions_total{reason="suspend"|"evict"}``), quota-pool
oversubscription (factor ≥ 2× physical chips admits more sessions than
inventory), the JWA suspended/resume surface — plus a randomized
suspend/resume property (no lost sessions, no double-booked chips,
restored state bit-identical) re-run under GRAFT_CHAOS-seeded faults.
"""

import random
import time

import pytest

from odh_kubeflow_tpu.apis import (
    LAST_ACTIVITY_ANNOTATION,
    RESUME_REQUESTED_ANNOTATION,
    STOP_ANNOTATION,
    SUSPEND_REASON_ANNOTATION,
    SUSPENDED_AT_ANNOTATION,
    TPU_ACCELERATOR_ANNOTATION,
    TPU_TOPOLOGY_ANNOTATION,
    register_crds,
)
from odh_kubeflow_tpu.controllers.culler import Culler, CullerConfig, _fmt_time
from odh_kubeflow_tpu.controllers.notebook import (
    NotebookController,
    NotebookControllerConfig,
)
from odh_kubeflow_tpu.controllers.runtime import Manager
from odh_kubeflow_tpu.machinery import objects as obj_util
from odh_kubeflow_tpu.machinery.faults import (
    FaultInjector,
    FaultSchedule,
    chaos_seed,
)
from odh_kubeflow_tpu.machinery.kubelet import FakeCluster
from odh_kubeflow_tpu.machinery.store import APIServer, NotFound
from odh_kubeflow_tpu.scheduling import (
    OVERSUBSCRIPTION_FACTOR_ANNOTATION,
    PRIORITY_CLASS_ANNOTATION,
    WORKLOAD_LABEL,
    register_scheduling,
)
from odh_kubeflow_tpu.scheduling.scheduler import SliceScheduler
from odh_kubeflow_tpu.sessions import register_sessions
from odh_kubeflow_tpu.sessions.checkpoint import SessionCheckpointStore
from odh_kubeflow_tpu.sessions.manager import SessionConfig, SessionManager
from odh_kubeflow_tpu.utils.prometheus import Registry, lint_metric_names

V5E = "tpu-v5-lite-podslice"
SEED = chaos_seed() or 20260803


# ---------------------------------------------------------------------------
# environment


def make_env(
    tmp_path,
    quota_chips=None,
    factor=None,
    pools=1,
    culling=False,
    suspend_on_cull=True,
    chaos=None,
    reclaim_idle_seconds=0.0,
):
    """The platform shape for session tests: notebook controller +
    session manager + suspender-wired scheduler over the embedded
    store, the kubelet sim providing the container hooks. ``chaos``
    (a FaultSchedule) inserts a seeded FaultInjector between the
    controllers and the store — the sim and assertions read raw truth."""
    api = APIServer()
    register_crds(api)
    register_scheduling(api)
    register_sessions(api)
    cluster = FakeCluster(api)
    registry = Registry()
    injector = None
    controller_api = api
    if chaos is not None:
        injector = FaultInjector(
            api,
            seed=SEED,
            schedule=chaos,
            registry=registry,
            sleep_fn=lambda _s: None,
        )
        controller_api = injector
    mgr = Manager(controller_api)
    store = SessionCheckpointStore(str(tmp_path / "ckpts"), backend="json")
    session_mgr = SessionManager(
        controller_api,
        SessionConfig(
            checkpoint_dir=str(tmp_path / "ckpts"),
            backend="json",
            reclaim_idle_seconds=reclaim_idle_seconds,
        ),
        registry=registry,
        runtime=cluster.session_runtime,
        store=store,
    )
    culler = (
        Culler(
            controller_api,
            CullerConfig(
                cull_idle_seconds=3600.0,
                idleness_check_seconds=0.0,
                suspend_on_cull=suspend_on_cull,
            ),
            base_url_fn=lambda nb: "http://127.0.0.1:9/unreachable",
        )
        if culling
        else None
    )
    ctrl = NotebookController(
        api=controller_api,
        config=NotebookControllerConfig(
            enable_queueing=True,
            enable_sessions=True,
            enable_culling=culling,
        ),
        registry=registry,
        culler=culler,
    )
    ctrl.register(mgr)
    session_mgr.register(mgr)
    scheduler = SliceScheduler(
        controller_api, registry=registry, suspender=session_mgr
    )
    scheduler.register(mgr)
    for i in range(pools):
        cluster.add_tpu_node_pool(
            f"pool-{i}", V5E, "2x2", num_hosts=1, chips_per_host=4
        )
    if quota_chips is not None:
        quota = {
            "apiVersion": "v1",
            "kind": "ResourceQuota",
            "metadata": {
                "name": "kf-resource-quota",
                "namespace": "team-a",
                "annotations": {},
            },
            "spec": {"hard": {"requests.google.com/tpu": str(quota_chips)}},
        }
        if factor is not None:
            quota["metadata"]["annotations"][
                OVERSUBSCRIPTION_FACTOR_ANNOTATION
            ] = str(factor)
        api.create(quota)
    return api, cluster, mgr, registry, session_mgr, culler, injector


def notebook(name, ns="team-a", priority_class=None):
    ann = {
        TPU_ACCELERATOR_ANNOTATION: V5E,
        TPU_TOPOLOGY_ANNOTATION: "2x2",
    }
    if priority_class:
        ann[PRIORITY_CLASS_ANNOTATION] = priority_class
    return {
        "apiVersion": "kubeflow.org/v1beta1",
        "kind": "Notebook",
        "metadata": {"name": name, "namespace": ns, "annotations": ann},
        "spec": {
            "template": {
                "spec": {"containers": [{"name": name, "image": "jax:latest"}]}
            }
        },
    }


def quiesce(cluster, mgr, rounds=4):
    from odh_kubeflow_tpu.machinery.store import APIError

    for _ in range(rounds):
        cluster.step()
        try:
            mgr.drain()
        except (RuntimeError, APIError):
            # under chaos a round may not quiesce, and an injected
            # fault inside a watch map function surfaces here; the
            # level-triggered retriggers + the converged end state are
            # what the invariants gate
            pass
        time.sleep(0.002)


def workload_state(api, name, ns="team-a"):
    try:
        return api.get("Workload", name, ns).get("status", {}).get("state", "")
    except NotFound:
        return None


def suspend(api, name, ns="team-a", reason="user"):
    now = obj_util.now_rfc3339()
    api.patch(
        "Notebook",
        name,
        {
            "metadata": {
                "annotations": {
                    STOP_ANNOTATION: now,
                    SUSPENDED_AT_ANNOTATION: now,
                    SUSPEND_REASON_ANNOTATION: reason,
                }
            }
        },
        ns,
    )


def resume(api, name, ns="team-a"):
    api.patch(
        "Notebook",
        name,
        {
            "metadata": {
                "annotations": {
                    STOP_ANNOTATION: None,
                    SUSPENDED_AT_ANNOTATION: None,
                    SUSPEND_REASON_ANNOTATION: None,
                    RESUME_REQUESTED_ANNOTATION: obj_util.now_rfc3339(),
                }
            }
        },
        ns,
    )


def bound_active_pods(api, name, ns="team-a"):
    return [
        p
        for p in api.list(
            "Pod",
            namespace=ns,
            label_selector={"matchLabels": {WORKLOAD_LABEL: name}},
        )
        if obj_util.get_path(p, "spec", "nodeName")
        and obj_util.get_path(p, "status", "phase")
        not in ("Succeeded", "Failed")
    ]


# ---------------------------------------------------------------------------
# checkpoint store


@pytest.mark.parametrize("backend", ["json", "orbax"])
def test_checkpoint_store_roundtrip_bit_identical(tmp_path, backend):
    if backend == "orbax":
        pytest.importorskip("orbax.checkpoint")
    store = SessionCheckpointStore(str(tmp_path), backend=backend)
    state = {"cells": [1, "two", {"three": 3.0}], "execution_count": 7}
    receipt = store.save("uid-a", state)
    assert receipt["step"] == 0 and receipt["sizeBytes"] > 0
    loaded, digest = store.load("uid-a")
    assert loaded == state
    assert digest == receipt["digest"]  # bit-identical receipt
    # re-suspend writes a new step; old steps are GC'd under max_to_keep
    receipt2 = store.save("uid-a", {"execution_count": 8})
    assert receipt2["step"] == 1
    loaded2, digest2 = store.load("uid-a")
    assert loaded2 == {"execution_count": 8} and digest2 == receipt2["digest"]
    assert store.exists("uid-a") and not store.exists("uid-b")
    store.delete("uid-a")
    assert not store.exists("uid-a")
    store.close()


# ---------------------------------------------------------------------------
# culler satellite: Suspended event, suspended-at annotation


def test_cull_with_suspend_emits_suspended_event_and_annotations(tmp_path):
    api, cluster, mgr, _, _, culler, _ = make_env(
        tmp_path, culling=True, suspend_on_cull=True
    )
    clock = {"now": 1_000_000.0}
    culler.now = lambda: clock["now"]
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    assert workload_state(api, "nb") == "Admitted"

    clock["now"] += 7200.0  # > cull_idle_seconds
    quiesce(cluster, mgr)
    nb = api.get("Notebook", "nb", "team-a")
    ann = obj_util.annotations_of(nb)
    assert STOP_ANNOTATION in ann
    assert SUSPENDED_AT_ANNOTATION in ann  # alongside, not instead
    assert ann[SUSPEND_REASON_ANNOTATION] == "cull"
    reasons = {
        e["reason"]
        for e in api.list("Event", namespace="team-a")
        if e["involvedObject"]["name"] == "nb"
    }
    assert "Suspended" in reasons  # the DISTINCT event
    assert "Culled" not in reasons


def test_cull_without_suspend_keeps_legacy_culled_event(tmp_path):
    api, cluster, mgr, _, _, culler, _ = make_env(
        tmp_path, culling=True, suspend_on_cull=False
    )
    clock = {"now": 1_000_000.0}
    culler.now = lambda: clock["now"]
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    clock["now"] += 7200.0
    quiesce(cluster, mgr)
    nb = api.get("Notebook", "nb", "team-a")
    ann = obj_util.annotations_of(nb)
    assert STOP_ANNOTATION in ann and SUSPENDED_AT_ANNOTATION not in ann
    reasons = {
        e["reason"]
        for e in api.list("Event", namespace="team-a")
        if e["involvedObject"]["name"] == "nb"
    }
    assert "Culled" in reasons and "Suspended" not in reasons


# ---------------------------------------------------------------------------
# suspend: snapshot before scale-down, reservation freed


def test_suspend_checkpoints_state_then_frees_slice_and_quota(tmp_path):
    api, cluster, mgr, _, session_mgr, _, _ = make_env(
        tmp_path, quota_chips=4
    )
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    assert workload_state(api, "nb") == "Admitted"
    cluster.set_session_state("team-a", "nb", {"counter": 42, "cells": [1, 2]})

    suspend(api, "nb", reason="cull")
    quiesce(cluster, mgr)

    ckpt = api.get("SessionCheckpoint", "nb", "team-a")
    assert ckpt["status"]["phase"] == "Suspended"
    assert ckpt["status"]["stateCaptured"] is True
    assert ckpt["spec"]["chips"] == 4
    # slice reservation freed: Workload deleted, pods gone
    assert workload_state(api, "nb") is None
    assert api.list("Pod", namespace="team-a") == []
    # the stored bytes match the recorded digest
    loaded, digest = session_mgr.store.load(
        api.get("Notebook", "nb", "team-a")["metadata"]["uid"]
    )
    assert loaded == {"counter": 42, "cells": [1, 2]}
    assert digest == ckpt["status"]["digest"]
    # quota released: a second notebook admits into the freed chips
    api.create(notebook("nb2"))
    quiesce(cluster, mgr)
    assert workload_state(api, "nb2") == "Admitted"


def test_scaledown_holds_until_checkpoint_is_durable(tmp_path):
    """Without a session manager completing the snapshot, a suspend
    request must NOT tear the pods down (the kernel state would be
    lost before it was saved) — the Workload keeps its reservation."""
    api = APIServer()
    register_crds(api)
    register_scheduling(api)
    register_sessions(api)
    cluster = FakeCluster(api)
    mgr = Manager(api)
    registry = Registry()
    NotebookController(
        api,
        NotebookControllerConfig(enable_queueing=True, enable_sessions=True),
        registry=registry,
    ).register(mgr)
    SliceScheduler(api, registry=registry).register(mgr)
    cluster.add_tpu_node_pool("a", V5E, "2x2", num_hosts=1, chips_per_host=4)
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    assert workload_state(api, "nb") == "Admitted"

    suspend(api, "nb")
    quiesce(cluster, mgr)
    # no manager took the snapshot → the hold is still on
    assert len(bound_active_pods(api, "nb")) == 1
    assert workload_state(api, "nb") == "Admitted"


def test_suspend_grace_degrades_to_plain_stop(tmp_path):
    """The wedge-breaker: a suspend whose snapshot never lands inside
    the grace window becomes a plain stop — chips must not leak."""
    api = APIServer()
    register_crds(api)
    register_scheduling(api)
    register_sessions(api)
    cluster = FakeCluster(api)
    mgr = Manager(api)
    registry = Registry()
    NotebookController(
        api,
        NotebookControllerConfig(
            enable_queueing=True,
            enable_sessions=True,
            suspend_grace_seconds=0.0,  # expire immediately
        ),
        registry=registry,
    ).register(mgr)
    SliceScheduler(api, registry=registry).register(mgr)
    cluster.add_tpu_node_pool("a", V5E, "2x2", num_hosts=1, chips_per_host=4)
    api.create(notebook("nb"))
    quiesce(cluster, mgr)

    suspend(api, "nb")
    time.sleep(0.01)
    quiesce(cluster, mgr)
    assert workload_state(api, "nb") is None  # reservation freed
    assert api.list("Pod", namespace="team-a") == []


def test_suspend_while_queued_records_empty_checkpoint(tmp_path):
    """Suspending a notebook that never ran (no pod to snapshot) must
    complete — with stateCaptured False — not wedge the scale-down."""
    api, cluster, mgr, _, _, _, _ = make_env(tmp_path, pools=1)
    api.create(notebook("holder"))
    quiesce(cluster, mgr)
    api.create(notebook("queued"))
    mgr.drain()  # queued behind holder; no pods bound
    suspend(api, "queued")
    quiesce(cluster, mgr)
    ckpt = api.get("SessionCheckpoint", "queued", "team-a")
    assert ckpt["status"]["phase"] == "Suspended"
    assert ckpt["status"]["stateCaptured"] is False
    assert any(
        e["reason"] == "SessionStateUnavailable"
        for e in api.list("Event", namespace="team-a")
    )


def test_resuspend_before_pod_runs_carries_checkpoint_forward(tmp_path):
    """A session re-suspended mid-resume (its fresh pod never came up)
    has no live kernel to snapshot — the previous durable checkpoint is
    still the truth and must survive the new epoch, not be overwritten
    by an empty one."""
    api, cluster, mgr, _, _, _, _ = make_env(tmp_path)
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    state = {"precious": True, "step": 9}
    cluster.set_session_state("team-a", "nb", state)
    suspend(api, "nb")
    quiesce(cluster, mgr)
    first = api.get("SessionCheckpoint", "nb", "team-a")["status"]
    assert first["stateCaptured"] is True

    # reopen, but re-suspend before the kubelet materialises the pod
    resume(api, "nb")
    mgr.drain()  # no cluster.step: Resuming, pod never Running
    suspend(api, "nb")
    quiesce(cluster, mgr)
    second = api.get("SessionCheckpoint", "nb", "team-a")["status"]
    assert second["phase"] == "Suspended"
    assert second["stateCaptured"] is True  # carried, not emptied
    assert second["digest"] == first["digest"]

    # and the eventual resume still restores the original kernel
    resume(api, "nb")
    quiesce(cluster, mgr, rounds=8)
    assert cluster.get_session_state("team-a", "nb") == state


# ---------------------------------------------------------------------------
# resume: warm restore before ready


def test_resume_restores_bit_identical_state_before_ready(tmp_path):
    api, cluster, mgr, registry, _, _, _ = make_env(tmp_path)
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    state = {"weights_hash": "abc123", "step": 1337, "history": list(range(16))}
    cluster.set_session_state("team-a", "nb", state)
    suspend(api, "nb")
    quiesce(cluster, mgr)
    assert workload_state(api, "nb") is None

    resume(api, "nb")
    quiesce(cluster, mgr, rounds=6)
    assert workload_state(api, "nb") == "Admitted"
    ckpt = api.get("SessionCheckpoint", "nb", "team-a")
    assert ckpt["status"]["phase"] == "Restored"
    # the fresh pod holds the exact pre-suspend kernel state
    assert cluster.get_session_state("team-a", "nb") == state
    # session phase cleared → JWA reports ready again
    nb = api.get("Notebook", "nb", "team-a")
    assert nb["status"].get("phase", "") == ""
    # warm-resume latency recorded
    text = registry.exposition()
    assert "session_resume_seconds_count 1" in text
    assert 'session_resumes_total{result="restored"} 1' in text
    assert any(
        e["reason"] == "Resumed"
        for e in api.list("Event", namespace="team-a")
    )


def test_resume_of_notebook_deleted_while_suspended_gcs_checkpoint(tmp_path):
    api, cluster, mgr, _, session_mgr, _, _ = make_env(tmp_path)
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    cluster.set_session_state("team-a", "nb", {"x": 1})
    suspend(api, "nb")
    quiesce(cluster, mgr)
    uid = api.get("Notebook", "nb", "team-a")["metadata"]["uid"]
    assert session_mgr.store.exists(uid)

    api.delete("Notebook", "nb", "team-a")
    quiesce(cluster, mgr)
    with pytest.raises(NotFound):
        api.get("SessionCheckpoint", "nb", "team-a")
    assert not session_mgr.store.exists(uid)  # stored bytes GC'd too


# ---------------------------------------------------------------------------
# scheduler satellite: suspendable victims first, suspend vs evict metrics


def test_preemption_suspends_suspendable_victim_instead_of_hard_kill(
    tmp_path,
):
    api, cluster, mgr, registry, _, _, _ = make_env(tmp_path, pools=1)
    for name, value in (("tpu-interactive", 1000), ("tpu-batch", -100)):
        api.create(
            {
                "apiVersion": "scheduling.k8s.io/v1",
                "kind": "PriorityClass",
                "metadata": {"name": name},
                "value": value,
                "globalDefault": False,
            }
        )
    api.create(notebook("batch", priority_class="tpu-batch"))
    quiesce(cluster, mgr)
    assert workload_state(api, "batch") == "Admitted"
    cluster.set_session_state("team-a", "batch", {"loss": 0.5})

    api.create(notebook("urgent", priority_class="tpu-interactive"))
    quiesce(cluster, mgr, rounds=6)
    # the victim was checkpoint-then-preempted, not hard-killed
    assert workload_state(api, "urgent") == "Admitted"
    ckpt = api.get("SessionCheckpoint", "batch", "team-a")
    assert ckpt["status"]["phase"] == "Suspended"
    assert ckpt["status"]["stateCaptured"] is True
    nb = api.get("Notebook", "batch", "team-a")
    assert (
        obj_util.annotations_of(nb)[SUSPEND_REASON_ANNOTATION] == "preempt"
    )
    text = registry.exposition()
    assert 'workload_preemptions_total{reason="suspend"} 1' in text
    assert 'workload_preemptions_total{reason="evict"}' not in text
    assert 'session_suspends_total{reason="preempt"} 1' in text


def test_hard_preemption_without_suspender_counts_evict(tmp_path):
    api = APIServer()
    register_crds(api)
    register_scheduling(api)
    cluster = FakeCluster(api)
    mgr = Manager(api)
    registry = Registry()
    NotebookController(
        api, NotebookControllerConfig(enable_queueing=True), registry=registry
    ).register(mgr)
    SliceScheduler(api, registry=registry).register(mgr)  # no suspender
    cluster.add_tpu_node_pool("a", V5E, "2x2", num_hosts=1, chips_per_host=4)
    for name, value in (("tpu-interactive", 1000), ("tpu-batch", -100)):
        api.create(
            {
                "apiVersion": "scheduling.k8s.io/v1",
                "kind": "PriorityClass",
                "metadata": {"name": name},
                "value": value,
                "globalDefault": False,
            }
        )
    api.create(notebook("batch", priority_class="tpu-batch"))
    quiesce(cluster, mgr)
    api.create(notebook("urgent", priority_class="tpu-interactive"))
    quiesce(cluster, mgr)
    assert workload_state(api, "urgent") == "Admitted"
    assert workload_state(api, "batch") == "Pending"
    assert (
        'workload_preemptions_total{reason="evict"} 1'
        in registry.exposition()
    )


def test_busy_session_is_not_reclaimed_at_equal_priority(tmp_path):
    """Equal-priority oversubscription reclaim only touches IDLE
    sessions: a recently-active kernel keeps its slice and the
    newcomer queues."""
    api, cluster, mgr, _, _, _, _ = make_env(
        tmp_path, pools=1, reclaim_idle_seconds=3600.0
    )
    api.create(notebook("busy"))
    quiesce(cluster, mgr)
    assert workload_state(api, "busy") == "Admitted"
    # the kernel reported activity moments ago
    api.patch(
        "Notebook",
        "busy",
        {
            "metadata": {
                "annotations": {
                    LAST_ACTIVITY_ANNOTATION: _fmt_time(time.time())
                }
            }
        },
        "team-a",
    )
    api.create(notebook("newcomer"))
    quiesce(cluster, mgr, rounds=6)
    assert workload_state(api, "busy") == "Admitted"
    assert workload_state(api, "newcomer") == "Pending"
    nb = api.get("Notebook", "busy", "team-a")
    assert SUSPENDED_AT_ANNOTATION not in obj_util.annotations_of(nb)


def test_high_priority_preempts_through_full_session_cap(tmp_path):
    """A pool at its committed-session ceiling must still honor strict
    priority: hard-evicting a lower-priority ACTIVE victim frees
    committed capacity (it requeues holding no checkpoint), so the
    high-priority workload admits — suspension would not help here."""
    api, cluster, mgr, registry, _, _, _ = make_env(
        tmp_path, quota_chips=4, factor=2, pools=1
    )
    for name, value in (("tpu-interactive", 1000), ("tpu-batch", -100)):
        api.create(
            {
                "apiVersion": "scheduling.k8s.io/v1",
                "kind": "PriorityClass",
                "metadata": {"name": name},
                "value": value,
                "globalDefault": False,
            }
        )
    # fill the cap: one suspended session (4) + one active batch (4) = 8
    api.create(notebook("parked"))
    quiesce(cluster, mgr)
    cluster.set_session_state("team-a", "parked", {"p": 1})
    suspend(api, "parked")
    quiesce(cluster, mgr)
    api.create(notebook("batch", priority_class="tpu-batch"))
    quiesce(cluster, mgr, rounds=6)
    assert workload_state(api, "batch") == "Admitted"

    api.create(notebook("urgent", priority_class="tpu-interactive"))
    quiesce(cluster, mgr, rounds=8)
    assert workload_state(api, "urgent") == "Admitted"
    assert workload_state(api, "batch") == "Pending"
    # the parked session was untouched — only eviction frees the cap
    assert (
        api.get("SessionCheckpoint", "parked", "team-a")["status"]["phase"]
        == "Suspended"
    )
    assert (
        'workload_preemptions_total{reason="evict"} 1'
        in registry.exposition()
    )


# ---------------------------------------------------------------------------
# oversubscription (acceptance criterion)


def test_oversubscribed_pool_admits_more_sessions_than_inventory(tmp_path):
    """ONE physical 4-chip slice, hard=4, factor=3: three 4-chip
    sessions are admitted over time (12 committed chips — 3× physical
    inventory) with idle ones suspending to make room; the fourth hits
    the session cap with a specific reason."""
    api, cluster, mgr, registry, session_mgr, _, _ = make_env(
        tmp_path, quota_chips=4, factor=3, pools=1
    )
    states = {}
    for i in (1, 2, 3):
        name = f"nb{i}"
        api.create(notebook(name))
        quiesce(cluster, mgr, rounds=8)
        assert workload_state(api, name) == "Admitted", name
        states[name] = {"owner": name, "payload": list(range(i))}
        cluster.set_session_state("team-a", name, states[name])

    # 3 sessions admitted against 4 physical chips: two are suspended,
    # one runs — committed exceeds inventory
    suspended = [
        ck
        for ck in api.list("SessionCheckpoint", namespace="team-a")
        if ck["status"]["phase"] == "Suspended"
    ]
    assert len(suspended) == 2
    committed = sum(ck["spec"]["chips"] for ck in suspended) + 4
    assert committed == 12  # 3× the 4-chip inventory

    # the fourth session exceeds hard × factor
    api.create(notebook("nb4"))
    quiesce(cluster, mgr, rounds=4)
    wl4 = api.get("Workload", "nb4", "team-a")
    assert wl4["status"]["state"] == "Pending"
    assert wl4["status"]["reason"] == "SessionCapExhausted"
    assert "oversubscription factor 3" in wl4["status"]["message"]

    # every suspended session resumes with its exact state (the live
    # one yields in turn — pure time-sharing of the single slice)
    api.delete("Notebook", "nb4", "team-a")
    for name in sorted(states):
        resume(api, name)
        quiesce(cluster, mgr, rounds=10)
        assert workload_state(api, name) == "Admitted", name
        assert cluster.get_session_state("team-a", name) == states[name]
        ckpt = api.get("SessionCheckpoint", name, "team-a")
        assert ckpt["status"]["phase"] == "Restored"
    # dashboards: the suspended-session gauge reflects the final state
    assert "suspended_sessions" in registry.exposition()


def test_suspended_sessions_do_not_hold_quota_without_factor(tmp_path):
    """Backward compatibility: a pool with NO oversubscription
    annotation keeps legacy semantics — suspended sessions are as
    invisible to admission as stopped notebooks."""
    api, cluster, mgr, _, _, _, _ = make_env(
        tmp_path, quota_chips=4, pools=2
    )
    api.create(notebook("first"))
    quiesce(cluster, mgr)
    suspend(api, "first")
    quiesce(cluster, mgr)
    api.create(notebook("second"))
    quiesce(cluster, mgr)
    assert workload_state(api, "second") == "Admitted"


# ---------------------------------------------------------------------------
# JWA surface


@pytest.fixture
def jwa_env(tmp_path, monkeypatch):
    from odh_kubeflow_tpu.web import crud_backend
    from odh_kubeflow_tpu.web.jwa import JupyterWebApp

    monkeypatch.setattr(crud_backend, "DEV_MODE", True)
    api, cluster, mgr, registry, session_mgr, _, _ = make_env(
        tmp_path, quota_chips=4, factor=2, pools=1
    )
    jwa = JupyterWebApp(api)
    server = jwa.app.serve()
    yield api, cluster, mgr, jwa, server
    server.shutdown()


def _call(server, method, path, body=None):
    import json as _json
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{server.server_port}{path}",
        method=method,
        data=_json.dumps(body).encode() if body is not None else None,
        headers={
            "kubeflow-userid": "alice@example.com",
            "Content-Type": "application/json",
            "Cookie": "XSRF-TOKEN=t",
            "X-XSRF-TOKEN": "t",
        },
    )
    import urllib.error

    try:
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status, _json.loads(r.read().decode() or "{}")
    except urllib.error.HTTPError as e:
        return e.code, _json.loads(e.read().decode() or "{}")


def test_jwa_distinguishes_suspended_from_stopped_and_resumes(jwa_env):
    api, cluster, mgr, jwa, server = jwa_env
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    cluster.set_session_state("team-a", "nb", {"k": "v"})

    # plain stop → "stopped"
    status, _ = _call(
        server,
        "PATCH",
        "/api/namespaces/team-a/notebooks/nb",
        {"stopped": True},
    )
    assert status == 200
    quiesce(cluster, mgr)
    row = jwa.notebook_row(api.get("Notebook", "nb", "team-a"))
    assert row["status"]["phase"] == "stopped"

    # start it again, then SUSPEND → "suspended", a different story
    _call(
        server,
        "PATCH",
        "/api/namespaces/team-a/notebooks/nb",
        {"stopped": False},
    )
    quiesce(cluster, mgr, rounds=6)
    cluster.set_session_state("team-a", "nb", {"k": "v2"})
    status, _ = _call(
        server,
        "PATCH",
        "/api/namespaces/team-a/notebooks/nb",
        {"stopped": True, "suspend": True},
    )
    assert status == 200
    quiesce(cluster, mgr)
    nb = api.get("Notebook", "nb", "team-a")
    row = jwa.notebook_row(nb)
    assert row["status"]["phase"] == "suspended"
    assert "resume" in row["status"]["message"]

    # resume endpoint: clears the contract, reports warm, restores
    status, body = _call(
        server, "POST", "/api/namespaces/team-a/notebooks/nb/resume"
    )
    assert status == 200 and body["resume"] == "warm"
    quiesce(cluster, mgr, rounds=6)
    assert cluster.get_session_state("team-a", "nb") == {"k": "v2"}
    row = jwa.notebook_row(api.get("Notebook", "nb", "team-a"))
    assert row["status"]["phase"] == "ready"
    ann = obj_util.annotations_of(api.get("Notebook", "nb", "team-a"))
    assert RESUME_REQUESTED_ANNOTATION in ann


def test_duplicate_suspend_patch_keeps_epoch_and_checkpoint(jwa_env):
    """A second suspend PATCH on an already-suspended notebook must be
    a no-op: no new epoch, no pod resurrection, the durable checkpoint
    untouched."""
    api, cluster, mgr, jwa, server = jwa_env
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    cluster.set_session_state("team-a", "nb", {"keep": "me"})
    _call(
        server,
        "PATCH",
        "/api/namespaces/team-a/notebooks/nb",
        {"stopped": True, "suspend": True},
    )
    quiesce(cluster, mgr)
    first_ckpt = api.get("SessionCheckpoint", "nb", "team-a")["status"]
    first_at = obj_util.annotations_of(
        api.get("Notebook", "nb", "team-a")
    )[SUSPENDED_AT_ANNOTATION]

    status, _ = _call(
        server,
        "PATCH",
        "/api/namespaces/team-a/notebooks/nb",
        {"stopped": True, "suspend": True},
    )
    assert status == 200
    quiesce(cluster, mgr, rounds=6)
    nb = api.get("Notebook", "nb", "team-a")
    assert obj_util.annotations_of(nb)[SUSPENDED_AT_ANNOTATION] == first_at
    second_ckpt = api.get("SessionCheckpoint", "nb", "team-a")["status"]
    assert second_ckpt["digest"] == first_ckpt["digest"]
    assert second_ckpt["suspendedAt"] == first_ckpt["suspendedAt"]
    assert api.list("Pod", namespace="team-a") == []  # no resurrection


def test_jwa_quota_block_surfaces_oversubscription(jwa_env):
    api, cluster, mgr, jwa, _ = jwa_env
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    cluster.set_session_state("team-a", "nb", {"s": 1})
    suspend(api, "nb")
    quiesce(cluster, mgr)
    api.create(notebook("nb2"))
    quiesce(cluster, mgr)
    q = jwa.tpu_quota("team-a")
    assert q["oversubscriptionFactor"] == "2"
    assert q["sessionCap"] == "8"
    assert q["suspended"] == "4"
    assert int(q["committed"]) == int(q["used"]) + 4


# ---------------------------------------------------------------------------
# the property (satellite): randomized suspend/resume under
# oversubscription — no lost sessions, no double-booked chips,
# bit-identical state


def _run_suspend_resume_property(tmp_path, chaos=None):
    from odh_kubeflow_tpu.analysis import sanitizer

    reports_before = len(sanitizer.reports())
    rng = random.Random(SEED)
    api, cluster, mgr, registry, session_mgr, _, injector = make_env(
        tmp_path,
        quota_chips=8,
        factor=3,  # 24 committed chips over 8 physical
        pools=2,
        chaos=chaos,
    )
    expected: dict[str, dict] = {}
    version = 0
    live: set[str] = set()
    counter = 0

    def running(name):
        try:
            pod = api.get("Pod", f"{name}-0", "team-a")
        except NotFound:
            return False
        return obj_util.get_path(pod, "status", "phase") == "Running"

    def write_fresh_state(name):
        nonlocal version
        nb = api.get("Notebook", name, "team-a")
        if SUSPENDED_AT_ANNOTATION in obj_util.annotations_of(nb):
            return  # snapshot may already be in flight — don't race it
        version += 1
        state = {"owner": name, "version": version}
        cluster.set_session_state("team-a", name, state)
        expected[name] = state

    def check_invariants():
        # 1. no double-booked chips: per-node bound usage within
        #    allocatable, and no partially-bound gang
        used_by_node: dict[str, float] = {}
        for pod in api.list("Pod"):
            node = obj_util.get_path(pod, "spec", "nodeName")
            if not node or obj_util.get_path(pod, "status", "phase") in (
                "Succeeded",
                "Failed",
            ):
                continue
            from odh_kubeflow_tpu.apis import pod_tpu_chips

            used_by_node[node] = used_by_node.get(node, 0) + pod_tpu_chips(
                pod
            )
        for node, used in used_by_node.items():
            assert used <= 4, f"node {node} double-booked: {used} chips"
        active_chips = 0
        for wl in api.list("Workload"):
            name = obj_util.name_of(wl)
            bound = len(bound_active_pods(api, name))
            assert bound in (0, wl["spec"]["hosts"]), f"partial gang {name}"
            if wl.get("status", {}).get("state") == "Admitted":
                active_chips += wl["spec"]["chips"]
        assert active_chips <= 8, "active sessions exceed quota hard cap"
        # 2. committed sessions within the oversubscription ceiling
        committed = active_chips + sum(
            ck["spec"]["chips"]
            for ck in api.list("SessionCheckpoint", namespace="team-a")
            # a checkpoint just created has no status yet
            if ck.get("status", {}).get("phase") in ("Suspended", "Resuming")
        )
        assert committed <= 24, f"committed {committed} chips > cap 24"
        # 3. no lost sessions: every live notebook is either active
        #    (workload exists) or durably checkpointed with its bytes
        #    loadable at the recorded digest
        for name in live:
            nb = api.get("Notebook", name, "team-a")
            ann = obj_util.annotations_of(nb)
            if SUSPENDED_AT_ANNOTATION not in ann:
                continue  # active or mid-transition: workload path owns it
            try:
                ck = api.get("SessionCheckpoint", name, "team-a")
            except NotFound:
                continue  # suspend requested, snapshot not yet taken
            if ck["status"].get("phase") not in ("Suspended",):
                continue
            if not ck["status"].get("stateCaptured"):
                continue
            loaded = session_mgr.store.load(nb["metadata"]["uid"])
            assert loaded is not None, f"lost session bytes for {name}"
            state, digest = loaded
            assert digest == ck["status"]["digest"], (
                f"{name}: stored bytes differ from checkpoint receipt"
            )
            if name in expected:
                assert state == expected[name], f"{name}: state drifted"

    for _ in range(22):
        op = rng.choice(["create", "suspend", "resume", "touch"])
        if op == "create" and len(live) < 5:
            counter += 1
            name = f"nb{counter}"
            api.create(notebook(name))
            live.add(name)
        elif op == "suspend" and live:
            name = rng.choice(sorted(live))
            nb = api.get("Notebook", name, "team-a")
            if SUSPENDED_AT_ANNOTATION not in obj_util.annotations_of(nb):
                suspend(api, name)
        elif op == "resume" and live:
            name = rng.choice(sorted(live))
            nb = api.get("Notebook", name, "team-a")
            if STOP_ANNOTATION in obj_util.annotations_of(nb):
                resume(api, name)
        elif op == "touch" and live:
            # the kernel computes: its memory changes while Running
            name = rng.choice(sorted(live))
            if running(name):
                write_fresh_state(name)
        quiesce(cluster, mgr, rounds=3)
        check_invariants()

    # weather clears (chaos runs only): everything must converge
    if injector is not None:
        injector.set_schedule(FaultSchedule.none())
        for _ in range(6):
            quiesce(cluster, mgr, rounds=2)
        check_invariants()

    # final sweep: resume every session in random order; each must come
    # back bit-identical, then yield the slice for the next
    order = sorted(live)
    rng.shuffle(order)
    for name in order:
        resume(api, name)
        for _ in range(12):
            quiesce(cluster, mgr, rounds=2)
            ck_phase = ""
            try:
                ck_phase = api.get("SessionCheckpoint", name, "team-a")[
                    "status"
                ].get("phase", "")
            except NotFound:
                pass
            if workload_state(api, name) == "Admitted" and ck_phase in (
                "",
                "Restored",
            ):
                break
        assert workload_state(api, name) == "Admitted", (
            f"{name} never resumed: {workload_state(api, name)}"
        )
        if name in expected:
            assert (
                cluster.get_session_state("team-a", name) == expected[name]
            ), f"{name}: resumed state not bit-identical"
        suspend(api, name)  # hand the slice to the next resume
        quiesce(cluster, mgr, rounds=3)
        check_invariants()

    if sanitizer.enabled():
        assert sanitizer.reports()[reports_before:] == []


def test_property_random_suspend_resume_oversubscribed(tmp_path):
    _run_suspend_resume_property(tmp_path)


def test_property_random_suspend_resume_under_chaos(tmp_path):
    """The same property with a seeded fault schedule on the
    controllers' API path (tests/test_chaos.py style): transient
    conflicts/429/5xx/watch drops must not lose a session, double-book
    a chip, or corrupt a checkpoint."""
    _run_suspend_resume_property(
        tmp_path,
        chaos=FaultSchedule(
            conflict=0.04,
            too_many_requests=0.03,
            server_error=0.02,
        ),
    )


# ---------------------------------------------------------------------------
# metrics lint (tier-1 guard)


def test_session_metric_families_and_naming_lint(tmp_path):
    api, cluster, mgr, registry, _, _, _ = make_env(tmp_path)
    api.create(notebook("nb"))
    quiesce(cluster, mgr)
    cluster.set_session_state("team-a", "nb", {"a": 1})
    suspend(api, "nb", reason="cull")
    quiesce(cluster, mgr)
    resume(api, "nb")
    quiesce(cluster, mgr, rounds=6)

    assert lint_metric_names(registry) == []
    text = registry.exposition()
    assert 'session_suspends_total{reason="cull"} 1' in text
    assert 'session_resumes_total{result="restored"} 1' in text
    assert "session_suspend_seconds_count 1" in text
    assert "session_resume_seconds_count 1" in text
    assert "session_checkpoint_size_bytes" in text
