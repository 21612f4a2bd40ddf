# Entry points for the checks CI runs (.github/workflows/ci.yml).
# `make check` is the one command a contributor needs before pushing.

PY ?= python

.PHONY: check lint typecheck test test-slow race baseline prof

check: lint typecheck test

# greptlint: project-invariant static analyzer (rules GL01-GL14;
# GL10-GL13 are interprocedural over the repo-wide call graph).
# Exit 0 requires a clean scan modulo .greptlint-baseline.json.
lint:
	$(PY) -m greptimedb_tpu.devtools.greptlint greptimedb_tpu/

# mypy is scoped by mypy.ini (common/, errors.py, utils/, devtools/).
# The build image does not ship mypy; skip with a notice rather than
# fail so `make check` works everywhere (CI installs it).
typecheck:
	@$(PY) -c "import mypy" 2>/dev/null \
	  && $(PY) -m mypy --config-file mypy.ini \
	  || echo "mypy not installed; skipping typecheck (see mypy.ini)"

# tier-1 suite as the driver runs it: six xdist workers, a file to a
# worker, under 1,470 s (lock-order detector is auto-enabled under
# pytest; greptlint runs inside as tests/test_greptlint.py)
test:
	timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
	  $(PY) -m pytest tests/ -q -m 'not slow' \
	  --continue-on-collection-errors -p no:cacheprovider \
	  -p xdist -n 6 --dist loadfile -p no:randomly

test-slow:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
	  --continue-on-collection-errors -p no:cacheprovider

# greptsan happens-before race detector, focused: the seeded selftest
# plus the multi-thread hammer (concurrent ingest+flush+compact+
# scatter+balancer+self-monitor) under an explicit GREPTIME_RACE_CHECK=1.
# The full `make test` run carries the detector too (auto-on under
# pytest); this target is the quick iteration loop for concurrency work.
race:
	GREPTIME_RACE_CHECK=1 JAX_PLATFORMS=cpu $(PY) -m pytest \
	  tests/test_greptsan.py tests/test_locks.py -q \
	  -p no:cacheprovider -p no:xdist -p no:randomly

# Re-record grandfathered findings. Only for CONSCIOUS grandfathering —
# the tier-1 gate asserts the baseline total only ever shrinks (≤ 10).
baseline:
	$(PY) -m greptimedb_tpu.devtools.greptlint greptimedb_tpu/ \
	  --write-baseline

# quick continuous-profiling demo: boots a standalone frontend with
# `SET profiling = 1`, runs a short mixed workload and prints the
# ADMIN SHOW PROFILE 'last' tree (ISSUE 17)
prof:
	JAX_PLATFORMS=cpu $(PY) -m pytest \
	  tests/test_profiler.py -q -k standalone_end_to_end \
	  -p no:cacheprovider -p no:xdist -p no:randomly
