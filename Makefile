# Top-level build/test fan-out (reference parity: components/Makefile:1-46
# fans docker-build over every component; here the components share one
# python package, so the fan-out is test tiers + image builds).

# NOTE: no PYTHONPATH export — on TPU hosts it can break accelerator
# plugin registration. Targets run from the repo root and use `-m`, so
# the cwd lands on sys.path instead.
PYTHON ?= python

.PHONY: all test test-unit test-manifests lint sanitize chaos durability explore fleetbench replicabench partitionbench overloadbench zonedrill usagebench warmbench obs loadtest images chip-smoke dryrun platform serve spawn-latency suspend-bench webbench native kind-smoke conformance

all: lint test

test: test-unit

test-unit:
	$(PYTHON) -m pytest tests/ -q

test-manifests:
	$(PYTHON) -m pytest tests/test_manifests.py -q

# one continuous capability sequence certifying the platform contract:
# register -> spawn -> ready -> share -> quota-reject -> cull ->
# restart -> preempt -> gang-restart -> elastic-resume -> delete
conformance:
	$(PYTHON) -m odh_kubeflow_tpu.conformance

# syntax check + graftlint: per-file AST invariant rules PLUS the
# whole-program call-graph rules (lock-order-cycle,
# blocking-reachable-under-lock, await-holding-lock) and the
# exception-flow rules (error-contract, handler-masks-fencing,
# dead-except) — see docs/GUIDE.md "Static analysis & concurrency
# discipline" and "Error contracts". Exit-code gated; fails only on
# findings NOT in analysis/baseline.json. The knob-registry lint
# cross-checks every os.environ knob against analysis/knobs.json,
# GUIDE.md, and manifest env stanzas.
lint:
	$(PYTHON) -m compileall -q odh_kubeflow_tpu tests loadtest chip_smoke.py __graft_entry__.py
	$(PYTHON) -m odh_kubeflow_tpu.analysis
	$(PYTHON) -m odh_kubeflow_tpu.analysis.knobs
	$(PYTHON) -m odh_kubeflow_tpu.analysis.protocol

# deterministic schedule explorer (docs/GUIDE.md "Deterministic
# schedule exploration"): seeded one-runnable-at-a-time interleavings
# of the group-commit pipeline (writers x committer x snapshot cut),
# lease-fencing handover, and informer heal-vs-read — plus the
# reverted historical races (rate-limiter sleep-under-lock, store
# apply-before-fsync) the explorer must re-find and replay from their
# printed seeds. GRAFT_SCHED=<n> multiplies the schedule budgets: the
# CI pyramid runs 1x, CI's dedicated explore step 3x; crank it for
# deeper local sweeps (`make explore GRAFT_SCHED=8`).
GRAFT_SCHED ?= 1
explore:
	GRAFT_SCHED=$(GRAFT_SCHED) $(PYTHON) -m pytest -q tests/test_schedule.py

# seeded chaos suite: resilience property tests under injected
# conflicts, 429s, 5xx, watch-stream drops, and resourceVersion expiry
# (GRAFT_CHAOS seeds every schedule — reproducible CI runs), with the
# concurrency sanitizer armed so recovery paths are race-probed too
chaos:
	GRAFT_CHAOS=1 GRAFT_SANITIZE=1 $(PYTHON) -m pytest -q \
	  tests/test_chaos.py tests/test_leader.py \
	  tests/test_sessions.py::test_property_random_suspend_resume_under_chaos \
	  tests/test_warmup.py::test_singleflight_dedups_concurrent_compiles \
	  tests/test_warmup.py::test_concurrent_claims_hand_out_exactly_one_standby

# crash/failover drills (docs/GUIDE.md "Durability & failover"): WAL
# kill-point sweep (process death at every commit point), disk-fault
# schedules (torn write / failed fsync / short read), fencing-token
# regression, and the sharded-manager failover drill — all under the
# sanitizer and a seeded chaos schedule — then the recovery axis of
# the control-plane bench (cold-recovery time + failover p99; writes
# to a scratch copy so the committed BENCH numbers change only when
# refreshed deliberately)
durability:
	GRAFT_SANITIZE=1 GRAFT_CHAOS=7 $(PYTHON) -m pytest -q \
	  tests/test_durability.py tests/test_leader.py \
	  tests/test_warmup.py::test_claim_kill_point_sweep_no_double_handout \
	  tests/test_warmup.py::test_cache_entries_survive_wal_failover
	cp BENCH_control_plane.json /tmp/durability_bench.json
	$(PYTHON) loadtest/control_plane_bench.py --recovery-only \
	  --recovery-counts 500,2000 --failover-reps 6 \
	  --out /tmp/durability_bench.json

# fleet-scale smoke (ISSUE 10): the 25k-notebook axis scaled down to
# N=2000 with the SAME gates — group-commit ingest >=5x the
# fsync-per-record baseline under 12 concurrent writers, paginated
# list p99 bounded with no page over the limit, watch fanout +
# admission-wait + cold-recovery recorded. Writes to a scratch copy so
# the committed BENCH numbers change only when refreshed deliberately
# (full run: `python loadtest/control_plane_bench.py --fleet
# --notebooks 25000`).
fleetbench:
	cp BENCH_control_plane.json /tmp/fleetbench.json
	$(PYTHON) loadtest/control_plane_bench.py --fleet --notebooks 2000 \
	  --fleet-watchers 50 --out /tmp/fleetbench.json
	$(PYTHON) -m pytest -q tests/test_fleet.py

# read-replica smoke (ISSUE 13): the 100k-notebook / 1000-stream axis
# scaled down to N=2000 with 2 followers and 100 streams, SAME gates —
# shipping must tax leader ingest <10%, follower state bit-identical,
# replica-served list p99 within the PR-10 leader-only bounds, sharded
# watch fanout p99 within the PR-10 26ms bound, staleness p99 <250ms
# under write load. Writes to a scratch copy (full run: `python
# loadtest/control_plane_bench.py --replica --notebooks 100000`).
replicabench:
	cp BENCH_control_plane.json /tmp/replicabench.json
	$(PYTHON) loadtest/control_plane_bench.py --replica --notebooks 2000 \
	  --replica-streams 100 --out /tmp/replicabench.json
	$(PYTHON) -m pytest -q tests/test_replica.py

# partitioned write path (ISSUE 18, docs/GUIDE.md "Partitioned write
# path"): the N=1M x 4-partition axis scaled down to N=2000 — real
# leader PROCESSES behind client-side HRW routing, SAME correctness
# gates (per-leader counts sum to N, merged limit/continue walk with
# composite tokens has zero order/duplicate violations, cluster-
# spanning merged watch delivers a post-ingest burst exactly once).
# The >=5x aggregate-ingest speedup gate only binds on hosts with
# >= 4 CPUs (leader compute cannot overlap on fewer cores); the
# measured ratio is always recorded. Writes to a scratch copy (full
# run: `python loadtest/control_plane_bench.py --partition
# --notebooks 1000000`).
partitionbench:
	cp BENCH_control_plane.json /tmp/partitionbench.json
	$(PYTHON) loadtest/control_plane_bench.py --partition --notebooks 2000 \
	  --partitions 4 --out /tmp/partitionbench.json
	$(PYTHON) -m pytest -q tests/test_partition.py

# overload-defense axis (docs/GUIDE.md "Overload defense"): the seeded
# metastable-failure drill — a 4x-capacity burst with one
# latency-poisoned partition — gated on burst goodput (>= 70% of
# baseline), retry amplification (<= 1.3x), system-traffic p99 under
# flood, recovery within 10s of burst end, and seed-exact replay;
# then the deadline/budget/breaker/priority unit suite under the
# sanitizer. Writes to a scratch copy of the bench JSON.
overloadbench:
	cp BENCH_control_plane.json /tmp/overloadbench.json
	$(PYTHON) loadtest/control_plane_bench.py --overload \
	  --out /tmp/overloadbench.json
	GRAFT_SANITIZE=1 $(PYTHON) -m pytest -q tests/test_overload.py

# zone failure-domain drills (docs/GUIDE.md "Zones & failure
# domains"): replicated-checkpoint write-all/heal, zone-spread
# placement, drain_zone checkpoint-then-migrate, NodeLost-storm
# escalation, the seeded zone-kill drill (one zone's checkpoint stores
# + nodes die mid-session; every suspended session resumes in the
# surviving zone bit-identical) and the promotion watchdog's hands-off
# failover — all under the sanitizer + a seeded chaos schedule, then
# the end-to-end two-act drill script
zonedrill:
	GRAFT_SANITIZE=1 GRAFT_CHAOS=17 $(PYTHON) -m pytest -q tests/test_zones.py
	GRAFT_SANITIZE=1 $(PYTHON) -m loadtest.zone_drill

# chip-hour metering drills (docs/GUIDE.md "Usage metering &
# showback"): the meter's unit invariants + activity-agent probe
# robustness under sanitizer + seeded chaos, the seeded
# accounting-exactness drill (lifecycle churn + wedged agent + WAL
# failover, ledger reconciled against a straight-line accountant to
# ε), then the metering-overhead axis of the control-plane bench
# (meter CPU per sampling window ≤2% of one core; writes to a scratch
# copy so committed BENCH numbers change only when refreshed
# deliberately)
# warm-start drills (docs/GUIDE.md "Compilation cache & warm pools"):
# the full warmup suite under the sanitizer (singleflight, corrupt
# artifact, TTL/LRU GC, zone fail/heal, WAL failover, claim race +
# kill-point sweep, zone-kill drain+backfill, JWA warm handout), then
# the gated cold-vs-warm bench — warm spawn must beat the cold spawn
# inside ONE sim run and the cache-service compile roundtrip must
# land the warm compile under 1s
warmbench:
	GRAFT_SANITIZE=1 $(PYTHON) -m pytest -q tests/test_warmup.py
	GRAFT_SANITIZE=1 $(PYTHON) -m loadtest.spawn_latency --warm-only

usagebench:
	GRAFT_SANITIZE=1 GRAFT_CHAOS=20591 $(PYTHON) -m pytest -q \
	  tests/test_usage.py tests/test_culler.py
	GRAFT_SANITIZE=1 $(PYTHON) -m loadtest.usage_drill
	cp BENCH_control_plane.json /tmp/usagebench.json
	$(PYTHON) loadtest/control_plane_bench.py --usage \
	  --out /tmp/usagebench.json

# the randomized property suites re-run as race probes: sanitized
# locks record acquisition order, re-entry, and blocking-under-lock
sanitize:
	GRAFT_SANITIZE=1 $(PYTHON) -m pytest -q \
	  tests/test_analysis.py \
	  tests/test_cache.py::test_cache_coherence_property_randomized_crud \
	  tests/test_scheduling.py::test_property_random_admit_preempt_node_loss_sequences \
	  tests/test_sessions.py::test_property_random_suspend_resume_oversubscribed

# observability smoke (docs/GUIDE.md "Tracing, zpages & SLOs"): spawn
# one notebook under a client trace against the sim platform and gate
# the whole surface — ONE assembled trace with the
# admission/gang-bind/container-start spans, OpenMetrics + trace-id
# exemplars under content negotiation (plain exposition byte-stable),
# SLO burn rates on /api/slo + slo_burn_rate gauges, /debug zpages
obs:
	$(PYTHON) -m loadtest.obs_smoke

# platform load test against the embedded apiserver + sim kubelet
# (loadtest/start_notebooks.py; reference notebook-controller/loadtest)
loadtest:
	$(PYTHON) -m loadtest.start_notebooks --count 20 --tpu

spawn-latency:
	$(PYTHON) -m loadtest.spawn_latency --record

# suspend → reopen → ready warm-resume gate (sessions/ subsystem): the
# cold platform spawn vs the checkpoint-backed resume, state verified
# bit-identical; runs on the sim kubelet, no accelerator needed
suspend-bench:
	$(PYTHON) -m loadtest.spawn_latency --suspend-only

# web-tier concurrency axis of the control-plane bench: thread-per-
# request + stdlib json baseline vs event loop + native serializer +
# bytes cache, over real sockets (gates >=10x concurrent req/s and no
# serial p99 regression; see docs/GUIDE.md "Async web tier")
webbench:
	$(PYTHON) loadtest/control_plane_bench.py

# C++ host-side components (input-pipeline packer + jsontree
# deepcopy/dumps); lazy-built on first import too — this target just
# front-loads the compiles
native:
	$(PYTHON) -c "from odh_kubeflow_tpu import native; so = native.build(force=True); \
	  import sys; print(so) if so else sys.exit('no C++ compiler found')"
	$(PYTHON) -c "from odh_kubeflow_tpu import native; import sys; \
	  ok = native.jsontree_deepcopy() and native.jsontree_dumps(); \
	  print('jsontree: deepcopy+dumps built') if ok else sys.exit('jsontree build failed')"

images:
	$(MAKE) -C images build

# the quickest proof that the system still starts on the chip: trainer
# + completion server at full Llama-3.2-1B size, one process, one
# device. Exits non-zero without a TPU (so: on the machine that has one)
chip-smoke:
	$(PYTHON) chip_smoke.py

# all-in-one platform with the sim kubelet (see docs/GUIDE.md)
platform:
	$(PYTHON) -m odh_kubeflow_tpu.platform --sim

# completion server in demo mode on the attached accelerator
serve:
	$(PYTHON) -m odh_kubeflow_tpu.models.serve --config llama3_1b --int8

# real-cluster smoke: build the platform container, load into KinD,
# apply manifests, require Notebook -> StatefulSet (needs docker+kind;
# CI runs the same flow in nb_controller_kind_test.yaml)
kind-smoke:
	kind create cluster --name kubeflow-tpu || true
	docker build -t odh-kubeflow-tpu/platform:latest -f images/platform/Dockerfile .
	kind load docker-image odh-kubeflow-tpu/platform:latest --name kubeflow-tpu
	kubectl create namespace kubeflow --dry-run=client -o yaml | kubectl apply -f -
	kubectl apply -f manifests/crds/ -f manifests/cluster-roles/ -f manifests/notebook-controller/
	kubectl -n kubeflow rollout status deployment/notebook-controller --timeout=180s

# multi-chip sharding compile check on a virtual 8-device CPU mesh
dryrun:
	$(PYTHON) -c \
	  "import importlib.util; \
	   s = importlib.util.spec_from_file_location('g', '__graft_entry__.py'); \
	   m = importlib.util.module_from_spec(s); s.loader.exec_module(m); \
	   m.dryrun_multichip(8)"
