# Common entry points (see README.md for details)
.PHONY: test test-fast denoise cookbook molecular tpu-checks obs-smoke serve-smoke serve-multi-smoke serve-fleet-smoke slo-smoke transport-smoke pipeline-smoke tune-smoke ring-smoke profile-smoke assembly-smoke mesh-smoke chaos-smoke train-chaos-smoke perf-gate clean-cache

test:              ## full suite on the simulated 8-device CPU mesh
	python -m pytest tests/ -q

test-fast:         ## <5-min single-core gate: kernel/math numerics + model smokes (skips slow + heavy tiers)
	python -m pytest tests/ -q -m "not slow and not heavy"

test-heavy:        ## the compile-heavy model-level integration tier
	python -m pytest tests/ -q -m "heavy"

denoise:           ## denoise training example
	python denoise.py --steps 20

cookbook:          ## every reference README usage pattern
	python examples/cookbook.py

molecular:         ## edge-conditioned molecular training example
	python examples/molecular_property.py

obs-smoke:         ## 3-step CPU denoise with telemetry: schema-gates the JSONL, renders the report (docs/OBSERVABILITY.md)
	python denoise.py --steps 3 --nodes 48 --accum 2 --cpu --telemetry --flush-every 2 --metrics /tmp/obs_smoke.jsonl
	python scripts/obs_report.py /tmp/obs_smoke.jsonl --validate --out /tmp/obs_smoke_summary.json

serve-smoke:       ## 3-request CPU serving run (2 buckets + 1 oversize reject): exits non-zero unless the telemetry stream is schema-valid AND zero post-warmup compiles fired
	rm -f /tmp/serve_smoke.jsonl
	python scripts/serve.py --requests 3 --oversize 1 --buckets 12,24 --batch-size 2 --cpu --metrics /tmp/serve_smoke.jsonl --out /tmp/serve_smoke_summary.json

serve-multi-smoke: ## 2-replica CPU continuous-batching gate: >=1 admission into an in-flight bucket slot, one mid-run rolling weight swap with zero dropped requests and zero post-warmup compiles, schema-valid stream (--require serve), and the serve perf budgets
	rm -f /tmp/serve_multi_smoke.jsonl
	python scripts/serve.py --replicas 2 --requests 16 --oversize 1 --swap-at 8 --buckets 12,24 --batch-size 2 --max-wait-ms 50 --cpu --metrics /tmp/serve_multi_smoke.jsonl --out /tmp/serve_multi_smoke_summary.json
	python scripts/obs_report.py /tmp/serve_multi_smoke.jsonl --validate --require serve --out /tmp/serve_multi_report.json
	python scripts/perf_gate.py /tmp/serve_multi_smoke.jsonl

pipeline-smoke:    ## 6-step pipelined CPU denoise (docs/PERFORMANCE.md): exits non-zero on schema violation or a 100% prefetch-stall rate
	rm -f /tmp/pipeline_smoke.jsonl
	python denoise.py --steps 6 --nodes 48 --accum 2 --cpu --pipelined --telemetry --flush-every 3 --metrics /tmp/pipeline_smoke.jsonl
	python scripts/obs_report.py /tmp/pipeline_smoke.jsonl --validate --require pipeline --out /tmp/pipeline_smoke_summary.json

tune-smoke:        ## interpret-mode kernel-autotuner mini-sweep on CPU (docs/PERFORMANCE.md "Kernel tuning"): exits non-zero unless the tune records are schema-valid AND a promoted entry is consulted on the next pick
	rm -rf /tmp/tune_smoke_cache /tmp/tune_smoke.jsonl
	SE3_TPU_CACHE_PATH=/tmp/tune_smoke_cache python scripts/tune_kernels.py --smoke --dry-run --max-targets 2 --out /tmp/tune_smoke.jsonl
	SE3_TPU_CACHE_PATH=/tmp/tune_smoke_cache python scripts/tune_kernels.py --smoke --max-targets 1 --max-candidates 1 --pairs 1 --steps 2 --margin -1 --out /tmp/tune_smoke.jsonl
	python scripts/obs_report.py /tmp/tune_smoke.jsonl --validate --require tune --out /tmp/tune_smoke_summary.json

ring-smoke:        ## virtual-8-device sequence-parallel comm gate (docs/PERFORMANCE.md "Sequence-parallel comms"): exchange-vs-dense parity + schema'd comm records + no full-width all-gather in the traced sp>1 exchange program
	rm -f /tmp/ring_smoke.jsonl
	python scripts/ring_smoke.py --metrics /tmp/ring_smoke.jsonl
	python scripts/obs_report.py /tmp/ring_smoke.jsonl --validate --require comm --out /tmp/ring_smoke_summary.json

profile-smoke:     ## toy trace (CPU) -> device time by the leaves of MODEL_SCOPES, read from the xplane (docs/PERFORMANCE.md "Reading rooflines"): exits non-zero unless they cover >=80% of device time AND the cost/profile records are schema-valid
	rm -f /tmp/profile_smoke.jsonl
	python scripts/profile_smoke.py --metrics /tmp/profile_smoke.jsonl --min-coverage 0.8
	python scripts/obs_report.py /tmp/profile_smoke.jsonl --validate --require cost,profile --out /tmp/profile_smoke_summary.json

assembly-smoke:    ## kNN-free large-assembly serving gate (docs/PERFORMANCE.md "Large assemblies"): global-vs-materialized parity + equivariance<=1e-5 on identical params, n=4096 SERVED through an AOT InferenceEngine global bucket (zero post-warmup compiles, oversize reject carries max_bucket), sp=2 ring arm proven all-gather-free from its partitioned HLO, >=3x streaming-vs-materialized peak-HBM off the cost ledger, schema'd assembly record judged by the committed budgets; then the --inject-regression arm must exit rc==1, proving those budgets fire
	rm -f /tmp/assembly_smoke.jsonl
	python scripts/assembly_smoke.py --metrics /tmp/assembly_smoke.jsonl
	python scripts/obs_report.py /tmp/assembly_smoke.jsonl --validate --require assembly --out /tmp/assembly_smoke_summary.json
	python scripts/perf_gate.py /tmp/assembly_smoke.jsonl
	rm -f /tmp/assembly_inject.jsonl
	python scripts/assembly_smoke.py --metrics /tmp/assembly_inject.jsonl --inject-regression >/tmp/assembly_inject.log 2>&1; test $$? -eq 1 || { echo "assembly-smoke injected arm did NOT fire with rc=1 — a vanished memory win / broken equivariance / unserved bucket went undetected; output:"; cat /tmp/assembly_inject.log; exit 1; }  # rc=1 is the committed budgets FIRING on the corrupted record; any other rc (crash, argparse, rc=2 budgets-not-wired) fails loudly with the evidence

mesh-smoke:        ## composed dp x sp x tp gate (docs/PERFORMANCE.md "Composed parallelism"): one composed (2,2,2) update matches dp-only (2,1,1) on the identical global problem to 1e-5, the flagship ring point compiles all-gather-free on the sequence axis WITH tp live (axis-aware HLO scan), the measured row banks as a schema'd mesh_sweep record (--require mesh_sweep) and the committed per-axis byte / memory / proof-bit budgets judge it; then the --inject-regression arm must exit rc==1, proving those budgets fire
	rm -f /tmp/mesh_smoke.jsonl
	python scripts/mesh_smoke.py --metrics /tmp/mesh_smoke.jsonl
	python scripts/obs_report.py /tmp/mesh_smoke.jsonl --validate --require mesh_sweep --out /tmp/mesh_smoke_summary.json
	rm -f /tmp/mesh_inject.jsonl
	python scripts/mesh_smoke.py --metrics /tmp/mesh_inject.jsonl --inject-regression >/tmp/mesh_inject.log 2>&1; test $$? -eq 1 || { echo "mesh-smoke injected arm did NOT fire with rc=1 — a sequence-rematerializing all-gather / per-axis byte blowup / memory regression went undetected; output:"; cat /tmp/mesh_inject.log; exit 1; }  # rc=1 is the committed budgets FIRING on the corrupted record; any other rc (crash, argparse, rc=2 budgets-not-wired) fails loudly with the evidence

chaos-smoke:       ## fault-domain gate (docs/ROBUSTNESS.md): seeded replica crashes + latency spikes + a torn latest checkpoint + one rolling swap over 3 CPU replicas — zero lost requests, >=1 observed quarantine->recovery, swap restores the FALLBACK step, schema'd fault records (--require fault), judged by the chaos perf budgets; then the WEAKENED arm (a fault class made droppable) must exit rc==1, proving the zero-lost gate fires
	rm -f /tmp/chaos_smoke.jsonl
	python scripts/chaos_smoke.py --metrics /tmp/chaos_smoke.jsonl --out /tmp/chaos_smoke_summary.json
	python scripts/obs_report.py /tmp/chaos_smoke.jsonl --validate --require fault,serve --out /tmp/chaos_smoke_report.json
	python scripts/perf_gate.py /tmp/chaos_smoke.jsonl
	python scripts/chaos_smoke.py --weaken drop >/tmp/chaos_weaken.log 2>&1; test $$? -eq 1 || { echo "chaos-smoke weakened arm did NOT fire with rc=1 — a droppable fault class went undetected; output:"; cat /tmp/chaos_weaken.log; exit 1; }  # rc=1 is the gate FIRING on lost requests; any other rc (crash, argparse) fails loudly with the evidence

serve-fleet-smoke: ## cross-host fleet gate (docs/ROBUSTNESS.md "Fleet fault domain"): 3 CPU host PROCESSES behind a FleetRouter — one SIGKILLed mid-run (requests redispatched cross-host, host quarantined, recovered via half-open probes after restart), seeded transport faults (latency + partition drop), and a poisoned-canary weight rollout that must AUTO-ROLL-BACK with zero sibling swaps — zero lost requests fleet-wide, zero post-warmup compiles, every host exits 0 on graceful SIGTERM, schema'd fleet records (--require fleet) judged by the fleet perf budgets; then the WEAKENED arm (host exclusion nulled) must exit rc==1, proving the gates fire
	rm -f /tmp/fleet_chaos.jsonl
	python scripts/fleet_chaos_smoke.py --metrics /tmp/fleet_chaos.jsonl --out /tmp/fleet_chaos_summary.json
	python scripts/obs_report.py /tmp/fleet_chaos.jsonl --validate --require fleet --out /tmp/fleet_chaos_report.json
	python scripts/perf_gate.py /tmp/fleet_chaos.jsonl
	python scripts/fleet_chaos_smoke.py --weaken noexclude >/tmp/fleet_weaken.log 2>&1; test $$? -eq 1 || { echo "serve-fleet-smoke weakened arm did NOT fire with rc=1 — nulled host exclusion went undetected; output:"; cat /tmp/fleet_weaken.log; exit 1; }  # rc=1 is the gates FIRING on the dead host eating traffic; any other rc (crash, argparse) fails loudly with the evidence

transport-smoke:   ## transport A/B gate (docs/ROBUSTNESS.md "Transport"): the SAME seeded closed-loop workload through legacy connect-per-call JSON vs pooled multiplexed binary framing — zero errors / frame errors / mid-run reconnects, in-flight depth > 1 (--require transport), and the committed wire-bytes ceiling judges the banked transport record (QPS and p99 ratios are recorded, not budgeted: a clock on this host is not speed); then the --inject-regression arm must exit rc==1, proving that budget fires
	rm -f /tmp/transport_ab.jsonl
	python scripts/transport_loadgen.py --metrics /tmp/transport_ab.jsonl
	python scripts/obs_report.py /tmp/transport_ab.jsonl --validate --require transport --out /tmp/transport_ab_report.json
	python scripts/perf_gate.py /tmp/transport_ab.jsonl
	rm -f /tmp/transport_inject.jsonl
	python scripts/transport_loadgen.py --metrics /tmp/transport_inject.jsonl --inject-regression >/tmp/transport_inject.log 2>&1; test $$? -eq 1 || { echo "transport-smoke injected arm did NOT fire with rc=1 — a JSON-fat wire went undetected; output:"; cat /tmp/transport_inject.log; exit 1; }  # rc=1 is the committed budgets FIRING on the corrupted record; any other rc (crash, argparse, rc=2 budgets-not-wired) fails loudly with the evidence

slo-smoke:         ## fleet observability gate (docs/OBSERVABILITY.md "Fleet dashboard"): 2 traced in-process hosts under seeded transport faults — every resolved request yields ONE complete single-root span tree (zero orphans), redispatched requests show multi-host traces reconciling with the cross_host_retries counter, merged-histogram fleet percentiles + availability land in schema'd trace/slo records (--require trace,slo), the dashboard renders, and the fleet perf budgets judge the stream; then the --inject-regression arm (fleet-side attempt spans discarded) must exit rc==1, proving the completeness gates fire
	rm -f /tmp/slo_smoke.jsonl
	python scripts/slo_smoke.py --metrics /tmp/slo_smoke.jsonl --out /tmp/slo_smoke_summary.json
	python scripts/obs_report.py /tmp/slo_smoke.jsonl --validate --require trace,slo --out /tmp/slo_smoke_report.json
	python scripts/slo_report.py /tmp/slo_smoke.jsonl --out /tmp/slo_dashboard.json
	python scripts/perf_gate.py /tmp/slo_smoke.jsonl
	python scripts/slo_smoke.py --metrics /tmp/slo_inject.jsonl --inject-regression >/tmp/slo_inject.log 2>&1; test $$? -eq 1 || { echo "slo-smoke injected arm did NOT fire with rc=1 — broken instrumentation (orphaned spans) went undetected; output:"; cat /tmp/slo_inject.log; exit 1; }  # rc=1 is the completeness gate FIRING on orphan spans; any other rc (crash, argparse) fails loudly with the evidence

train-chaos-smoke: ## self-healing training gate (docs/ROBUSTNESS.md "Training fault domain"): an injected-NaN step + a real mid-run SIGTERM over the guarded elastic loop — the run must roll back (>=1 observed), exit resumable, resume, and finish BIT-EXACT vs an uninterrupted control arm with zero post-warmup recompiles; schema'd guard records (--require guard: injections >= 1, diverged == false), judged by the train-chaos perf budgets; then the WEAKENED arm (rollback nulled) must exit rc==1, proving the diverged gate fires
	rm -f /tmp/train_chaos.jsonl
	python scripts/train_chaos_smoke.py --metrics /tmp/train_chaos.jsonl --out /tmp/train_chaos_summary.json
	python scripts/obs_report.py /tmp/train_chaos.jsonl --validate --require guard --out /tmp/train_chaos_report.json
	python scripts/perf_gate.py /tmp/train_chaos.jsonl
	python scripts/train_chaos_smoke.py --weaken norollback >/tmp/train_chaos_weaken.log 2>&1; test $$? -eq 1 || { echo "train-chaos-smoke weakened arm did NOT fire with rc=1 — a nulled rollback went undetected; output:"; cat /tmp/train_chaos_weaken.log; exit 1; }  # rc=1 is the diverged gate FIRING; any other rc (crash, argparse) fails loudly with the evidence

perf-gate:         ## committed budgets vs the evidence streams (docs/PERFORMANCE.md "The perf gate"): must PASS on the current tree, then must FIRE on an injected synthetic regression
	python scripts/perf_gate.py --fresh-cost /tmp/perf_gate_cost.jsonl
	python scripts/perf_gate.py /tmp/perf_gate_cost.jsonl --inject-regression >/tmp/perf_gate_inject.log 2>&1; test $$? -eq 1 || { echo "perf-gate injection arm did NOT fire with rc=1 — gate output:"; cat /tmp/perf_gate_inject.log; exit 1; }  # rc=1 is the gate FIRING; any other rc (argparse error, crash) fails loudly with the evidence

tpu-checks:        ## on-chip equivariance + kernel numerics/speed gate
	python scripts/tpu_checks.py

clean-cache:       ## wipe the Q_J, kernel-table and jit caches
	rm -rf .jax_cache
