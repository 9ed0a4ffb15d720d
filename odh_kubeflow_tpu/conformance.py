"""Platform conformance gate (reference: conformance/1.5/README.md —
the upstream program certifies a distribution by running its component
test suites; this rebuild certifies the live platform contract in one
continuous sequence instead of per-component snippets).

One run drives every platform capability end to end against the
embedded control plane — each step both asserts its own transitions
and sets up the next, so a pass certifies the capabilities *compose*:

    register → spawn (TPU slice) → ready → share (kfam) →
    quota-reject a second slice → cull (idle) → restart →
    preempt → gang restart → elastic train resume → delete (cascade)

Run it via ``make conformance`` or ``python -m
odh_kubeflow_tpu.conformance``; it prints a one-line capability
scorecard and exits non-zero on the first broken transition.
``tests/test_conformance.py`` wires it into the suite/CI.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

from odh_kubeflow_tpu.apis import (
    LAST_ACTIVITY_ANNOTATION,
    STOP_ANNOTATION,
    TPU_ACCELERATOR_ANNOTATION,
    TPU_TOPOLOGY_ANNOTATION,
    register_crds,
)
from odh_kubeflow_tpu.controllers.culler import Culler, CullerConfig, _fmt_time
from odh_kubeflow_tpu.controllers.kfam import KfamService, binding_name
from odh_kubeflow_tpu.controllers.notebook import (
    NotebookController,
    NotebookControllerConfig,
)
from odh_kubeflow_tpu.controllers.profile import (
    ProfileController,
    TPU_QUOTA_KEY,
)
from odh_kubeflow_tpu.controllers.runtime import Manager
from odh_kubeflow_tpu.machinery.kubelet import FakeCluster
from odh_kubeflow_tpu.machinery.store import APIServer, NotFound

OWNER = "alice@example.com"
NS = "team-conf"


class _IdleJupyter(BaseHTTPRequestHandler):
    """Fake Jupyter API reporting an idle kernel last active at epoch
    ``idle_since`` — what the culler's real HTTP probe reads."""

    idle_since = 0.0

    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        if self.path.endswith("/api/kernels"):
            body = [{
                "execution_state": "idle",
                "last_activity": _fmt_time(type(self).idle_since),
            }]
        elif self.path.endswith("/api/terminals"):
            body = []
        else:
            self.send_response(404)
            self.end_headers()
            return
        data = json.dumps(body).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *args):
        pass


def _notebook(name: str) -> dict:
    return {
        "apiVersion": "kubeflow.org/v1beta1",
        "kind": "Notebook",
        "metadata": {
            "name": name,
            "namespace": NS,
            "annotations": {
                TPU_ACCELERATOR_ANNOTATION: "tpu-v5-lite-podslice",
                TPU_TOPOLOGY_ANNOTATION: "2x2",
            },
        },
        "spec": {
            "template": {
                "spec": {"containers": [{"name": name, "image": "jax:tpu"}]}
            }
        },
    }


def run_conformance(verbose: bool = False) -> dict:
    """Run the full capability sequence; returns the scorecard dict
    (step → "PASS"). Raises AssertionError at the first transition that
    does not hold, with the failing step named."""
    scorecard: dict = {}

    def step(name):
        def mark(_result=None):
            scorecard[name] = "PASS"
            if verbose:
                print(f"conformance: {name} PASS", flush=True)

        return mark

    clock = {"now": time.time()}
    now_fn = lambda: clock["now"]  # noqa: E731

    server = HTTPServer(("127.0.0.1", 0), _IdleJupyter)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    jupyter_url = f"http://127.0.0.1:{server.server_port}"

    try:
        api = APIServer()
        register_crds(api)
        cluster = FakeCluster(api)
        # one v5e 2x2 host pool: 4 chips — exactly one slice's worth,
        # so the second spawn must trip the profile's quota
        cluster.add_tpu_node_pool(
            "v5e", "tpu-v5-lite-podslice", "2x2", num_hosts=2,
            chips_per_host=4,
        )
        mgr = Manager(api, time_fn=now_fn)
        culler = Culler(
            api,
            CullerConfig(cull_idle_seconds=600, idleness_check_seconds=60),
            base_url_fn=lambda nb: jupyter_url,
            now_fn=now_fn,
        )
        NotebookController(
            api, NotebookControllerConfig(enable_culling=True), culler=culler
        ).register(mgr)
        ProfileController(api).register(mgr)
        kfam = KfamService(api, cluster_admins={"root@example.com"})

        # 1. register — a Profile materialises the tenant: namespace,
        # owner rolebinding, service account, TPU chip quota
        api.create({
            "apiVersion": "kubeflow.org/v1",
            "kind": "Profile",
            "metadata": {"name": NS},
            "spec": {
                "owner": {"kind": "User", "name": OWNER},
                "resourceQuotaSpec": {"hard": {TPU_QUOTA_KEY: "4"}},
            },
        })
        mgr.drain()
        api.get("Namespace", NS)
        assert (
            api.get("ResourceQuota", "kf-resource-quota", NS)["spec"]["hard"][
                TPU_QUOTA_KEY
            ]
            == "4"
        )
        step("register")()

        # 2. spawn — TPU notebook: STS + headless svc + scheduled pod
        api.create(_notebook("nb1"))
        mgr.drain()
        cluster.step()
        mgr.drain()
        sts = api.get("StatefulSet", "nb1", NS)
        limits = sts["spec"]["template"]["spec"]["containers"][0][
            "resources"
        ]["limits"]
        assert limits["google.com/tpu"] == "4"
        step("spawn")()

        # 3. ready — pod Running, status mirrored onto the CR
        nb = api.get("Notebook", "nb1", NS)
        assert nb["status"]["readyReplicas"] == 1, nb["status"]
        assert api.get("Pod", "nb1-0", NS)["status"]["phase"] == "Running"
        step("ready")()

        # 4. share — the owner grants a contributor via kfam
        kfam.create_binding(
            {
                "user": {"kind": "User", "name": "bob@example.com"},
                "referredNamespace": NS,
                "roleRef": {
                    "apiGroup": "rbac.authorization.k8s.io",
                    "kind": "ClusterRole",
                    "name": "kubeflow-edit",
                },
            },
            requester=OWNER,
        )
        api.get("RoleBinding", binding_name("bob@example.com", "edit"), NS)
        assert kfam.namespaces_for_user("bob@example.com") == [NS]
        step("share")()

        # 5. quota-reject — a second slice would exceed the tenant's
        # 4-chip quota: the pod must never materialise and the denial
        # must be observable
        api.create(_notebook("nb2"))
        mgr.drain()
        cluster.step()
        mgr.drain()
        try:
            api.get("Pod", "nb2-0", NS)
            raise AssertionError("quota-exceeding pod was created")
        except NotFound:
            pass
        denials = [
            e
            for e in api.list("Event", namespace=NS)
            if e["reason"] == "FailedCreate"
            and "exceeded quota" in e["message"]
        ]
        assert denials, "no quota denial event"
        api.delete("Notebook", "nb2", NS)
        mgr.drain()
        step("quota-reject")()

        # 6. cull — idle past the threshold: the culler stamps
        # last-activity, sets the stop annotation, STS scales to zero
        _IdleJupyter.idle_since = clock["now"]
        clock["now"] += 61  # past the check period: the probe runs and
        mgr.drain()         # stamps last-activity while the pod is up
        clock["now"] += 700  # > cull_idle_seconds of reported idleness
        mgr.drain()  # the cull decision
        cluster.step()
        mgr.drain()
        nb = api.get("Notebook", "nb1", NS)
        anns = nb["metadata"]["annotations"]
        assert STOP_ANNOTATION in anns, anns.keys()
        assert LAST_ACTIVITY_ANNOTATION in anns
        assert api.get("StatefulSet", "nb1", NS)["spec"]["replicas"] == 0
        step("cull")()

        # 7. restart — clearing the stop annotation brings it back
        api.patch(
            "Notebook", "nb1",
            {"metadata": {"annotations": {STOP_ANNOTATION: None}}}, NS,
        )
        mgr.drain()
        cluster.step()
        mgr.drain()
        assert api.get("Pod", "nb1-0", NS)["status"]["phase"] == "Running"
        step("restart")()

        # 8. preempt — GKE reclaims the slice host: SlicePreempted
        # condition + warning event + gang teardown
        node = api.get("Pod", "nb1-0", NS)["spec"]["nodeName"]
        cluster.preempt_node(node)
        mgr.drain()
        nb = api.get("Notebook", "nb1", NS)
        conds = {c["type"]: c for c in nb["status"]["conditions"]}
        assert conds["SlicePreempted"]["status"] == "True"
        step("preempt")()

        # 9. gang-restart — capacity returns, the group re-materialises
        cluster.add_tpu_node_pool(
            "v5e-b", "tpu-v5-lite-podslice", "2x2", num_hosts=1,
            chips_per_host=4,
        )
        mgr.drain()
        cluster.step()
        mgr.drain()
        assert api.get("Pod", "nb1-0", NS)["status"]["phase"] == "Running"
        step("gang-restart")()

        # 10. elastic-resume — the training story the platform hosts:
        # preemption forces a checkpoint, a fresh trainer resumes from
        # it and finishes (single-process here; the 8-process version
        # is tests/test_distributed_gang.py)
        import tempfile

        import jax

        from odh_kubeflow_tpu.models import LlamaConfig, LoraConfig
        from odh_kubeflow_tpu.train import TrainConfig, Trainer
        from odh_kubeflow_tpu.train.checkpoint import CheckpointManager
        from odh_kubeflow_tpu.train.elastic import PreemptionGuard, run_elastic

        with tempfile.TemporaryDirectory() as ckpt_dir:
            cfg = LlamaConfig.tiny()
            tr = Trainer(
                cfg, TrainConfig(warmup_steps=1, total_steps=100),
                lora_cfg=LoraConfig(rank=2),
            )
            manager = CheckpointManager(ckpt_dir, save_interval_steps=2)
            guard = PreemptionGuard().install()

            def batches(tr):
                while True:
                    yield tr.make_fake_batch(
                        len(jax.devices()), 16
                    )

            def preempt_at_3(step_num, _metrics):
                if step_num >= 3:
                    guard._stop.set()  # the SIGTERM latch, delivered

            out = run_elastic(
                tr, manager, batches(tr), total_steps=10,
                on_step=preempt_at_3, guard=guard,
            )
            guard.uninstall()
            assert out["preempted"] and out["step"] >= 3
            tr2 = Trainer(
                cfg, TrainConfig(warmup_steps=1, total_steps=100),
                lora_cfg=LoraConfig(rank=2),
            )
            manager2 = CheckpointManager(ckpt_dir, save_interval_steps=2)
            out2 = run_elastic(
                tr2, manager2, batches(tr2), total_steps=6,
            )
            assert out2["resumed_from"] is not None
            assert out2["step"] == 6 and not out2["preempted"]
            # flush async orbax writes before the tempdir vanishes
            manager.wait_until_finished()
            manager2.wait_until_finished()
        step("elastic-resume")()

        # 11. delete — owner cascade removes everything the CR owns
        api.delete("Notebook", "nb1", NS)
        mgr.drain()
        for kind, name in (
            ("StatefulSet", "nb1"),
            ("Service", "nb1"),
            ("Pod", "nb1-0"),
        ):
            try:
                api.get(kind, name, NS)
                raise AssertionError(f"{kind}/{name} survived deletion")
            except NotFound:
                pass
        step("delete")()

        mgr.stop()
    finally:
        server.shutdown()

    return scorecard


def main() -> int:
    import jax

    # control-plane logic + a tiny trainer: CPU is the right venue even
    # when a TPU is attached (deterministic, and the chip stays free)
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already initialised; run where we are
    try:
        scorecard = run_conformance(verbose=False)
    except (AssertionError, NotFound) as e:
        # name the broken transition: everything after the last PASS
        print(f"conformance: FAIL — {type(e).__name__}: {e}")
        return 1
    line = " ".join(f"{k}={v}" for k, v in scorecard.items())
    print(
        f"conformance: {line} ({len(scorecard)}/{len(scorecard)})"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
