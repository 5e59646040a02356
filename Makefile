# Developer entry points. PYTHONPATH is set per-target so the targets
# work from a clean checkout with no install step.

PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

ROUNDTRIP_DIR ?= /tmp/repro-serve-roundtrip
ROUNDTRIP_ARGS = --engine all --compare-codecs --n-docs 400 --n-queries 8 --seed 0

.PHONY: test check bench bench-fast docs-check serve-roundtrip kernel-parity shard-parity mutation-parity overlap-parity value-parity pipeline-smoke clean

test:            ## tier-1 suite (the CI gate)
	$(PY) -m pytest -x -q

docs-check:      ## audit DESIGN/EXPERIMENTS § cross-references + README make targets
	$(PY) tools/docs_check.py

serve-roundtrip: ## artifact lifecycle smoke: build→save, then load→search in a fresh process (byte-identical top-k, every engine×codec)
	rm -rf $(ROUNDTRIP_DIR)
	$(PY) -m repro.launch.serve $(ROUNDTRIP_ARGS) --save-index $(ROUNDTRIP_DIR)
	$(PY) -m repro.launch.serve $(ROUNDTRIP_ARGS) --load-index $(ROUNDTRIP_DIR)
	rm -rf $(ROUNDTRIP_DIR)

kernel-parity:   ## fused kernels vs jnp in both pallas modes: block scan, rows rescoring, 3-mode top-k id parity, HBM accounting — all engines×codecs
	$(PY) tools/kernel_parity.py

shard-parity:    ## sharded vs unsharded byte-identical top-k (ragged shards included), mmap'd artifact round-trip, on-disk bytes bound — all engines×codecs
	$(PY) tools/shard_parity.py

mutation-parity: ## live-mutation gate: delta segments + tombstones + crash-safe merge byte-identical to a fresh oracle build, pre- and post-merge, monolithic + sharded — all engines×codecs; then a seeded mutate-under-traffic load generator
	$(PY) tools/mutation_parity.py
	$(PY) -m repro.launch.serve --mutate --engine flat --codec streamvbyte --n-docs 60 --n-queries 6 --k 5 --mutations 9

overlap-parity:  ## overlapped serving invisible in the bytes: prefetch on/off parity + the prefetcher actually staging, mesh with live tombstones vs the sequential rotation, queries racing a background merge through the commit flip — all engines×codecs
	$(PY) tools/overlap_parity.py

value-parity:    ## value-codec gate: vq="f16" byte-identical to legacy packs, 3-mode top-k parity at every engine×codec×vq, quantized top-k overlap floors vs the f16 oracle
	$(PY) tools/value_parity.py

pipeline-smoke:  ## micro-batching scheduler smoke: synthetic trace through the pipeline, every response byte-identical to direct search, ServeStats report
	$(PY) -m repro.launch.serve --pipeline --engine flat --codec streamvbyte --n-docs 300 --n-queries 16 --requests 96 --deadline-us 500
	$(PY) -m repro.launch.serve --pipeline --engine seismic --codec dotvbyte --backend pallas --n-docs 400 --n-queries 8 --requests 48 --n-probe 16

check: docs-check serve-roundtrip kernel-parity shard-parity mutation-parity overlap-parity value-parity pipeline-smoke ## tier-1 suite + tiny Table-1..7+kernel benchmark pass + docs audit + artifact + parity + mutation + overlap + value + pipeline gates
	$(PY) -m benchmarks.run --quick

bench:           ## full benchmark sweep (slow)
	$(PY) -m benchmarks.run

bench-fast:      ## reduced-size benchmark sweep
	$(PY) -m benchmarks.run --fast

clean:           ## remove stray bytecode, tool caches, and mutable-index artifacts (generation dirs + CURRENT pointers)
	find . -type d -name __pycache__ -prune -exec rm -rf {} +
	find . -type f \( -name '*.pyc' -o -name '*.pyo' \) -delete
	find . -type d -name 'generation_[0-9][0-9][0-9][0-9]' -prune -exec rm -rf {} +
	find . -type f -name CURRENT -delete
	rm -rf .pytest_cache .ruff_cache .mypy_cache
