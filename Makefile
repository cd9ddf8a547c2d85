# One-command CI gate — the analog of the reference's travis_script.sh
# (scripts/travis/travis_script.sh:39-66: gtest suite + TSAN task).
#
#   make check        lints + pytest + native unit tests + sanitizers +
#                     fuzz + native parse bench, logged to CHECK.log
#                     (dated) — the full pre-commit gate
#   make test         pytest only (fast inner loop)
#   make sanitize     ASan/UBSan + TSan native runs -> native/SANITIZE.log
#   make native-test  plain build + run of the C++ unit smoke (skips with
#                     a notice when no toolchain is present)
#   make parse-bench  native scanner throughput tool (no device needed)
#   make fuzz         mutation fuzz of every native parse C-ABI entry point
#                     (crash-safety; DMLC_FUZZ_ITERS to scale)
#   make lint-retry   grep gate: no time.sleep inside retry-shaped loops
#                     outside dmlc_tpu/io/resilience.py (ad-hoc retry
#                     loops must delegate to the shared RetryPolicy)
#   make lint-metrics grep gate: no direct COUNTERS.bump / ad-hoc
#                     time.monotonic() stage timing outside
#                     dmlc_tpu/utils/{telemetry,timer}.py (bookkeeping
#                     must live on the telemetry registry/span tracer)
#   make lint-store   grep gate: no direct os.replace / hand-allocated
#                     .tmp publish of store-managed artifact formats
#                     outside dmlc_tpu/store/ (publish must go through
#                     the tiered artifact store — docs/store.md)

PYTHON ?= python
# the native core's translation units — keep in sync with the other three
# lists: native/CMakeLists.txt, native/run_sanitizers.sh SRCS, and
# dmlc_tpu/native/__init__.py _SRCS (the on-demand .so build)
NATIVE_SRCS = native/src/parse.cc native/src/reader.cc \
	native/src/recordio.cc
# bash + pipefail so a failing stage is never masked by the tee into CHECK.log
SHELL := /bin/bash
.SHELLFLAGS := -o pipefail -c

.PHONY: check test test-all sanitize parse-bench fuzz \
	lint-retry lint-metrics lint-store native-test

# the tier-1 contract: slow-marked scale/soak tests are opt-in (test-all)
test:
	$(PYTHON) -m pytest tests/ -q -m 'not slow'

test-all:
	$(PYTHON) -m pytest tests/ -q

lint-retry:
	$(PYTHON) bin/lint_retry.py

lint-metrics:
	$(PYTHON) bin/lint_metrics.py

lint-store:
	$(PYTHON) bin/lint_store.py

fuzz:
	$(PYTHON) native/test/fuzz_parse.py

sanitize:
	sh native/run_sanitizers.sh

# plain (unsanitized) build + run of the C++ unit smoke — the fast native
# gate `make check` runs on any host with a toolchain; hosts without g++
# skip with a notice instead of failing (the Python suites still cover
# behavior through the prebuilt .so when one exists)
native-test:
	@if command -v g++ >/dev/null 2>&1; then \
	    mkdir -p native/build && \
	    g++ -O2 -std=c++17 -pthread -o native/build/native_smoke \
	        native/test/native_smoke.cc $(NATIVE_SRCS) && \
	    ./native/build/native_smoke; \
	else \
	    echo "native-test: g++ not found, skipping native unit tests"; \
	fi

parse-bench:
	mkdir -p native/build
	g++ -O3 -std=c++17 -pthread -o native/build/parse_bench \
	    native/test/parse_bench.cc $(NATIVE_SRCS)
	@test -f native/build/bench_corpus.libsvm || $(PYTHON) -c "import random; \
	    r = random.Random(7); \
	    f = open('native/build/bench_corpus.libsvm', 'w'); \
	    [f.write(str(i % 2) + ' ' + ' '.join(f'{j}:{r.random():.6f}' \
	        for j in range(28)) + '\n') for i in range(40000)]"
	./native/build/parse_bench native/build/bench_corpus.libsvm 28 3

check:
	@echo "== make check $$(date -u +%Y-%m-%dT%H:%M:%SZ) ==" | tee CHECK.log
	@echo "-- lint-retry (ad-hoc retry loop gate) --" | tee -a CHECK.log
	$(MAKE) --no-print-directory lint-retry 2>&1 | tee -a CHECK.log
	@echo "-- lint-metrics (ad-hoc bookkeeping gate) --" | tee -a CHECK.log
	$(MAKE) --no-print-directory lint-metrics 2>&1 | tee -a CHECK.log
	@echo "-- lint-store (direct artifact-publish gate) --" | tee -a CHECK.log
	$(MAKE) --no-print-directory lint-store 2>&1 | tee -a CHECK.log
	@echo "-- pytest --" | tee -a CHECK.log
	$(PYTHON) -m pytest tests/ -q -m 'not slow' 2>&1 | tee -a CHECK.log
	@echo "-- native unit tests --" | tee -a CHECK.log
	$(MAKE) --no-print-directory native-test 2>&1 | tee -a CHECK.log
	@echo "-- sanitizers --" | tee -a CHECK.log
	sh native/run_sanitizers.sh 2>&1 | tee -a CHECK.log
	@echo "-- parse fuzz --" | tee -a CHECK.log
	$(PYTHON) native/test/fuzz_parse.py 2>&1 | tee -a CHECK.log
	@echo "-- parse bench --" | tee -a CHECK.log
	$(MAKE) --no-print-directory parse-bench 2>&1 | tee -a CHECK.log
	@echo "== make check: ALL GREEN ==" | tee -a CHECK.log
