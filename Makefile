# Developer entry points.  The tier-1 verify command is `make test`.
PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

# Parallelize the suite across cores when pytest-xdist is installed (CI
# installs it via requirements-dev.txt; bare containers fall back to serial).
# The probe result is cached in .cache/xdist.mk — re-probed only when
# requirements-dev.txt or the active interpreter changes (the stamp records
# which interpreter it probed; `command -v` is a shell builtin, not another
# Python spawn per invocation), never on unrelated make targets.
XDIST :=
PYBIN := $(shell command -v $(PY))
-include .cache/xdist.mk
ifneq ($(XDIST_PY),$(PYBIN))
# stale cache from a different interpreter: drop the flag and re-probe
XDIST :=
.cache/xdist.mk: FORCE
endif
.cache/xdist.mk: requirements-dev.txt
	@mkdir -p .cache
	@echo 'XDIST_PY := $(PYBIN)' > $@
	@if $(PY) -c "import xdist" >/dev/null 2>&1; then \
	  echo 'XDIST := -n auto' >> $@; \
	else \
	  echo 'XDIST :=' >> $@; \
	fi
FORCE:

.PHONY: test test-slow test-sharded lint bench-smoke bench \
	report-gate dev-deps

test:            ## tier-1 test suite (the verify gate for every PR; excludes slow-marked tests)
	$(PY) -m pytest -x -q -m "not slow" $(XDIST)

# 8 faked host devices (the flag must be set before jax imports, hence a
# fresh interpreter): the superstep differential + sharded-vs-single-device
# equivalence tests actually exercise the shard_map path here, instead of
# skipping on the single-device default.
test-sharded:    ## superstep differential + sharding tests under 8 faked host devices
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -m pytest -x -q -m "not slow" \
	  tests/test_superstep.py tests/test_metrics_stream.py

test-slow:       ## pixel-path + hypothesis-heavy tests (nightly-blocking, per-PR non-blocking CI job)
	$(PY) -m pytest -q -m slow

lint:            ## ruff check (CI blocks on this; skipped when ruff is absent)
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
	  $(PY) -m ruff check src tests benchmarks examples; \
	else \
	  echo "ruff not installed (run 'make dev-deps'); skipping lint"; \
	fi

# One process for every preset (`--scenario all` embeds the per-scenario
# smoke overrides incl. the pixel frontend for pixel_city) instead of five
# sequential interpreters each paying import + jit warmup.  Writes INTO
# reports/ — this is how the committed baselines are (re)blessed.
bench-smoke:     ## fast end-to-end sanity; regenerates per-scenario JSON baselines in reports/
	$(PY) examples/run_scenarios.py --scenario all --cameras 4 --duration 30 --json-out reports
	$(PY) examples/quickstart.py

# Inside GitHub Actions the gate also appends a per-metric verdict table
# to the job summary page; local runs (no GITHUB_STEP_SUMMARY) skip it.
SUMMARY_FLAG = $(if $(GITHUB_STEP_SUMMARY),--summary-md "$(GITHUB_STEP_SUMMARY)")

REPORT_FRESH := .cache/reports-fresh
report-gate:     ## regenerate all scenario reports into a scratch dir and diff against committed reports/ baselines (tolerance bands; fails on breach)
	rm -rf $(REPORT_FRESH)
	$(PY) examples/run_scenarios.py --scenario all --cameras 4 --duration 30 --json-out $(REPORT_FRESH)
	$(PY) benchmarks/report_gate.py --fresh $(REPORT_FRESH) --baseline reports $(SUMMARY_FLAG)

bench:           ## full paper tables/figures (fine-tunes the workload; slow)
	$(PY) -m benchmarks.run

dev-deps:        ## install test/dev dependencies
	pip install -r requirements-dev.txt
