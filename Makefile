# babble-tpu build/dev targets (reference: makefile — glide/go build becomes
# pytest/demo orchestration; there is nothing to compile).

PY ?= python3
N ?= 4

.PHONY: test lint race status-smoke smoke smoke-tiny bench bench-mesh bench-ingest bench-packed trend soak dist wheel-proof demo-conf demo demo-watch demo-bombard multichip version

test:
	$(PY) -m pytest tests/ -q

# concurrency certification (ISSUE 12, docs/analysis.md): the full tier-1
# suite under lockset/lock-order instrumentation (BABBLE_RACE_CERTIFY=1
# wraps the session in analysis/lockruntime.certify()), then the 50-seed
# sim sweep under the same instrumentation via the lint CLI. Zero race
# candidates and an acyclic lock graph are the acceptance bar.
RACE_SEEDS ?= 50
race:
	BABBLE_RACE_CERTIFY=1 $(PY) -m pytest tests/ -q -m 'not slow'
	$(PY) -m babble_tpu lint --races --race-seeds $(RACE_SEEDS)

# consensus-grade static analysis (babble_tpu/analysis/, docs/analysis.md):
# determinism lint + lock-discipline checker + JAX staging audit +
# staged-kernel contract checker (--staged: kernel-* rules over tpu/) +
# observability lint (obs-*: static metric names, literal label sets).
# Hard gate, with a hard <30s wall-time budget so it stays cheap enough
# to run on every edit. ruff/mypy are an advisory second tier — they run
# only where installed (pip install -e '.[lint]'); the container image
# does not ship them.
lint:
	@start=$$(date +%s); \
	$(PY) -m babble_tpu lint --staged || exit 1; \
	elapsed=$$(( $$(date +%s) - start )); \
	if [ "$$elapsed" -ge 30 ]; then \
		echo "lint: FAIL — hard gate took $${elapsed}s, over the 30s wall-time budget"; \
		exit 1; \
	fi
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check babble_tpu/; \
	else \
		echo "lint: ruff not installed — skipping advisory tier"; \
	fi
	@if command -v mypy >/dev/null 2>&1; then \
		mypy --config-file pyproject.toml || true; \
	else \
		echo "lint: mypy not installed — skipping advisory tier"; \
	fi

# cluster health plane end-to-end (ISSUE 20, docs/observability.md):
# 3-node in-proc cluster -> digest piggyback over live gossip -> GET
# /debug/cluster + /health/digest over TCP -> the `babble-tpu status`
# renderer must show 3 nodes at zero skew, full agreement, no suspicion
status-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/status_smoke.py

# the quickest proof that the served path runs on the chip: strict, needs
# a TPU (exit 3 without one), one process per chip. Through the chip tool:
# `chiprun -- python3 chip_smoke.py`
smoke:
	$(PY) chip_smoke.py

# the same phases at toy sizes on XLA:CPU (the dry run before spending
# chip time; 4 virtual devices so the mesh4 phase runs too)
smoke-tiny:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 $(PY) chip_smoke.py --tiny

bench:
	$(PY) bench.py

# validator sweep across dispatch disciplines (round-batched mesh rung);
# archived as BENCH_MESH_r*.json, gated by the trend series below
bench-mesh:
	$(PY) bench_mesh_scale.py --slo

# bit-packed voting-table bench (ISSUE 17): the same validator sweep with
# the packed discipline as the headline — wide-vs-packed byte-equality
# gate per rung and the packed-speedup SLO floor from 1024 validators up
# (the floor objective arms only when the sweep reaches --slo-packed-n;
# the default CPU sweep stays under it because the WIDE baseline at 1024
# already exhausts host memory on the 8-device virtual mesh — run
# `--validators 64,256,1024` on real hardware to arm the crossover gate);
# archived as BENCH_PACKED_r*.json, gated by the trend series below
bench-packed:
	$(PY) bench_mesh_scale.py --headline packed --validators 8,64,128 --slo

# open-loop ingest bench (ISSUE 16): offered load through the ingress
# pipeline on the sim fabric, gated on submit->commit p50/p99 and on
# batched-vs-single-tx digest equality; archived as BENCH_INGEST_r*.json
bench-ingest:
	$(PY) bench_ingest.py --slo

# cross-round perf-trend gate over the archived BENCH_*_r*/MULTICHIP_r*
# artifacts (all CPU-platform records today; a series with fewer than two
# rounds has nothing to gate): fails on a >10% regression against the
# best prior round
trend:
	$(PY) scripts/bench_trend.py

# adversarial-timing fast-sync soak (VERDICT r3 #5): chained-donor
# fast-forward + device-engine reattach scenarios with stall diagnostics
soak:
	$(PY) scripts/soak_fastsync.py all --iters 10

# wheel build (reference: makefile:5-21 / scripts/dist.sh); docker/
# installs from dist/
dist:
	$(PY) -m pip wheel --no-deps --no-build-isolation -w dist .

# install-and-run from the wheel in a clean venv: 2 nodes + bots from the
# console script, committed byte-identical blocks over HTTP (VERDICT r4 #9)
wheel-proof:
	./scripts/prove_wheel.sh

multichip:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

demo-conf:
	./demo/build-conf.sh $(N)

demo: demo-conf
	./demo/run-testnet.sh $(N)

demo-watch:
	./demo/watch.sh $(N)

demo-bombard:
	./demo/bombard.sh $(N)

version:
	$(PY) -m babble_tpu version
