# Dev / deploy automation, mirroring the reference's Makefile:24-56 target
# chain (docker-compose bring-up, db-schema load, tests) and the
# ccdc.install.example:86-94 run aliases — minus the Spark/Maven machinery
# the TPU runtime doesn't have.

COMPOSE := docker compose -f deploy/docker-compose.yml
# Tile example: CONUS Albers point inside tile h=20 v=11.
X ?= 542000
Y ?= 1650000
ACQUIRED ?= 1982-01-01/2017-12-31

.PHONY: install lint test bench obs-smoke pipeline-smoke chaos-smoke \
        fleet-smoke elastic-smoke serve-smoke pyramid-smoke serve-fleet \
        compact-smoke postmortem-smoke alert-smoke streamfleet-smoke \
        telemetry-smoke slo-smoke wire-smoke objectstore-smoke \
        fanout-smoke fanout-proof \
        image db-up db-schema db-test db-down changedetection \
        classification clean

install:
	pip install -e . --no-build-isolation

# Static contract checker (docs/STATIC_ANALYSIS.md): the jax-hotpath,
# knob-registry, metrics-contract, and thread-ownership rule families
# over the repo itself.  Fails on findings NOT absorbed by the committed
# lint_baseline.json; the JSON summary lands in FIREBIRD_LINT_DIR
# (default /tmp/fb_lint) where bench.py folds it into round artifacts.
lint:
	python -m firebird_tpu.analysis \
	  --json "$${FIREBIRD_LINT_DIR:-/tmp/fb_lint}/lint_report.json"

# The default verify path runs the contract checker first: a knob/metric/
# hotpath/ownership drift fails the build before the (slower) test suite —
# then the alerting end-to-end drill (the smoke tier's representative:
# it exercises stream + serve + fleet queue together under chaos).
test: lint
	python -m pytest tests/ -x -q
	$(MAKE) pyramid-smoke
	$(MAKE) alert-smoke
	$(MAKE) streamfleet-smoke
	$(MAKE) telemetry-smoke
	$(MAKE) slo-smoke
	$(MAKE) objectstore-smoke
	$(MAKE) fanout-smoke
	$(MAKE) elastic-smoke

bench:
	python bench.py

# End-to-end telemetry check: synthetic-source driver run with the span
# tracer on AND the ops endpoint bound to an ephemeral port — polls
# /healthz /readyz /metrics /progress while batches are in flight, then
# validates the emitted Chrome-trace JSON and obs_report.json against
# the schema + stage-key contract (docs/OBSERVABILITY.md).
obs-smoke:
	python tools/obs_smoke.py

# Zero-stall pipeline check: tiny end-to-end changedetection on CPU with
# input staging + bulk batch egress + the persistent compile cache on,
# twice — asserts the obs report carries the stage/egress histograms with
# nonzero counts and that run 2 hits the compile cache (no XLA recompile).
pipeline-smoke:
	python tools/pipeline_smoke.py

# Graceful-degradation check (docs/ROBUSTNESS.md): a synthetic tile run
# under a seeded fault plan (ingest p=0.05 + a poisoned chip + a store
# brownout), then `--resume` — asserts the poisoned chip lands in
# quarantine.json without failing its chunk, the quarantine drains, and
# the final store is row-for-row identical to a clean run.
chaos-smoke:
	python tools/chaos_soak.py

# Fleet-queue chaos check (docs/ROBUSTNESS.md "Fleet scheduling"): a
# multi-tile plan drained by worker subprocesses with one SIGKILLed
# mid-lease and one heartbeat-partitioned (lease:p=1 zombie) — asserts
# the survivors drain every job, the zombie's stale-fence writes are
# rejected (counter nonzero, zero accepted), and the merged store is
# row-identical to a clean single-worker run.
fleet-smoke:
	python tools/fleet_chaos.py

# Elastic-fleet chaos check (docs/ROBUSTNESS.md "Elastic operation"): a
# full 726-tile CONUS plan (tiny synthetic chips) drained by the
# autoscaling supervisor at 10x any prior soak's worker count, with
# random worker SIGKILLs, a heartbeat-partitioned zombie, and the
# supervisor itself killed + restarted mid-drain — asserts the restart
# ADOPTS orphaned workers (no double-spawn), every job drains, zero
# stale-fence writes are accepted (store row-identical to a clean
# serial leg), and the fleet scales back to zero afterwards.  The
# scale-decision log lands in the artifact (folded by bench.py).
elastic-smoke:
	python tools/elastic_soak.py

# Serving-layer check (docs/SERVING.md): tiny synthetic run into a
# sqlite store, then the query API on an ephemeral port — every endpoint
# exercised with values cross-checked against products.save output, 8
# concurrent identical cold misses proven to coalesce into ONE
# computation, cache hits proven, and the closed-loop loadtest artifact
# (RPS, p50/p95/p99, hit rate) written + folded by bench.py.
serve-smoke:
	python tools/serve_smoke.py

# Pyramid + changefeed coherence check (docs/SERVING.md): seed a store,
# build a 2-level quadkey pyramid — base tiles byte-compared against
# products.save rasters — then mutate one chip through the
# product_writes feed and assert EXACTLY the ancestor tiles go stale
# and the old ETag's 304 flips to a fresh 200; artifact folded by
# bench.py alongside the serve loadtest.
pyramid-smoke:
	python tools/pyramid_smoke.py

# Multi-replica read-path bench (docs/SERVING.md): seed + pyramid, then
# N `firebird serve` replicas (read-only mode=ro store connections)
# behind a round-robin front door under a mixed hot/cold/304/SSE
# workload from multi-process client shards, with a live writer
# mutating mid-test — the artifact carries aggregate RPS, p50/p95/p99,
# hit/304 rates, and max observed staleness vs the changefeed bound.
# Heavier than the smoke tier (spawns a process fleet), so not part of
# `make test`; bench.py folds the artifact when it exists.
serve-fleet:
	python tools/serve_loadtest.py --fleet 10 --requests 400000 \
	  --client-procs 12 --concurrency 5 --mutations 6 --sse 4 \
	  --feed-poll 0.5

# Crash flight-recorder check (docs/OBSERVABILITY.md "Flight recorder"):
# a subprocess run SIGTERM'd mid-batch must die with real SIGTERM
# semantics AND leave a parseable postmortem.json (per-thread event
# rings, breaker/quarantine state, config fingerprint), and `--resume`
# must recover the store row-for-row identical to an uninterrupted run.
postmortem-smoke:
	python tools/postmortem_smoke.py

# Active-lane compaction check (docs/ROOFLINE.md "Occupancy"): the same
# synthetic tile with compaction on vs off — asserts the stores are
# byte-identical, the loop actually compacted (kernel_compactions > 0),
# and wasted lane-rounds dropped at least 2x; artifact folded by bench.py.
compact-smoke:
	python tools/compact_smoke.py

# Wire-diet regression probe (docs/ROOFLINE.md "Wire budget"): one
# staged batch on CPU — asserts every staged ingress plane is integer
# (no float h2d), the egress tables are int-coded and decode bit-exactly,
# and the packed drain is measurably smaller than the raw f32 fetch;
# artifact folded by bench.py.
wire-smoke:
	python tools/wire_probe.py

# Alerting end-to-end drill (docs/ALERTS.md): a streaming run over a
# step-change archive with injected ingest faults and a SIGKILL
# mid-stream — asserts zero lost alerts, zero duplicates after the
# resume, webhook delivery catching up from its durable cursor, repair
# jobs enqueued once per broken chip and drained by a fleet worker, and
# an evaluated acquisition→alert-visible freshness SLO in the artifact
# (folded by bench.py).
alert-smoke:
	python tools/alert_soak.py

# Streaming-first end-to-end drill (docs/STREAMING.md): a standing
# fleet — `firebird watch` + N `fleet work --forever` workers — drains
# synthetic scenes as they land on the manifest, with the watcher AND
# one worker SIGKILLed mid-drain; asserts every scene processed exactly
# once across watcher incarnations, every alert delivered exactly once,
# the packed tile statestore byte-identical to a clean serial leg, and
# the acquisition→alert freshness SLO evaluated over real observations
# (artifact folded by bench.py next to the e2e block).
streamfleet-smoke:
	python tools/stream_fleet_soak.py

# Fleet telemetry-plane drill (docs/OBSERVABILITY.md "Fleet telemetry
# plane"): a standing watcher + 2-worker fleet over a landing zone, the
# worker holding the alerting job SIGKILLed mid-lease, a separate
# deliverer process pushing the webhook backlog — then `firebird trace
# collect` must merge every process's spool (including the SIGKILLed
# one's recovered segments) into ONE Perfetto trace where the alerting
# scene's trace id crosses >=4 OS processes, with a per-alert
# critical-path breakdown summing to the measured
# acquisition_to_alert_seconds within 10%; a FIREBIRD_TELEMETRY=0 leg
# proves disarmed telemetry writes nothing (artifact folded by bench.py).
telemetry-smoke:
	python tools/telemetry_smoke.py

# Object-tier chaos drill (docs/ROBUSTNESS.md "Object tier"): the
# chunked conditional-put protocol, 3-way store parity (plain sqlite /
# env-armed mirror / pure object backend row-identical), stale object
# fences rejected 100% with a durable census, torn uploads (truncated
# chunk, dropped manifest) recovered by generation fallback, a SIGKILL
# between chunk upload and manifest commit leaving no visible partial
# object, and the orphan scrubber reclaiming the debris; statestore and
# pyramid object legs ride along (artifact folded by bench.py).
objectstore-smoke:
	python tools/objectstore_chaos.py

# Fanout-plane drill (docs/ALERTS.md "Fanout plane"): quadkey-sharded
# subscription index + fleet-powered delivery at a scaled-down tier —
# audience resolution must stay flat across subscriber milestones
# (index vs brute-force scan), a 10k-pair burst must land exactly-once
# through a fanout worker SIGKILLed mid-drain (0 duplicate re-POSTs by
# record id), digest/batch policies must flush, and shard-job
# completion p99 must beat the fanout_p99 budget leg; artifact folded
# by bench.py.  `fanout-proof` is the full 1M-subscriber / 10k-alert
# headline run (several minutes — not part of `make test`).
fanout-smoke:
	python tools/fanout_loadtest.py --subscribers 50000 --alerts 2000 \
	  --workers 3

fanout-proof:
	python tools/fanout_loadtest.py

# Error-budget plane drill (docs/OBSERVABILITY.md "Error budgets"):
# fleet + black-box canary prober; injected serve brownout + watcher
# stall must trip the multi-window burn verdict durably, and metric
# history must survive a SIGKILLed serving process + a prober restart.
slo-smoke:
	python tools/slo_smoke.py

image:
	docker build -f deploy/Dockerfile -t firebird .

# ---- results store (reference Makefile:24-39 docker-up + db-schema) ----

db-up:
	$(COMPOSE) up -d --wait cassandra

# Apply the generated DDL (`firebird schema`) through the container's
# cqlsh — the reference pipes resources/schema.cql the same way.
db-schema:
	firebird schema | $(COMPOSE) exec -T cassandra cqlsh

# Gated live round-trip test against the composed Cassandra (skips
# cleanly when the service is unreachable).
db-test:
	CASSANDRA=127.0.0.1 CASSANDRA_PORT=9043 \
	python -m pytest tests/test_cassandra_live.py -v

db-down:
	$(COMPOSE) down

# ---- run aliases (ccdc.install.example:86-94) ----

changedetection:
	firebird changedetection -x $(X) -y $(Y) -a $(ACQUIRED)

classification:
	firebird classification -x $(X) -y $(Y) -s 724204 -e 735598

clean:
	rm -rf build dist *.egg-info .pytest_cache
	find . -name __pycache__ -prune -exec rm -rf {} +
