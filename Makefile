# Top-level entry points for the static correctness layer and the native
# test matrix (docs/static-analysis.md). CI drop-in: scripts/ci_checks.sh
# chains the lot with a summary table; every target here exits non-zero on
# any finding.

NATIVE := horovod_tpu/native

# The full static gate: cross-language invariant linter (env vars, docs,
# enum mirrors, atomics-ordering discipline, C-API<->ctypes parity), the
# thread-role contract checker, ruff (if installed), clang-tidy and clang
# thread-safety analysis (both skip with a notice when clang is absent —
# CI-only there; the two python checkers and tests always run).
lint: invariants threadroles ruff tidy analyze

invariants:
	python3 scripts/check_invariants.py

threadroles:
	python3 scripts/check_threadroles.py

# Python lint ([tool.ruff] in pyproject.toml). Graceful skip keeps `make
# lint` usable on boxes without ruff; CI installs it.
ruff:
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check horovod_tpu/ scripts/check_invariants.py scripts/check_threadroles.py tests/test_static_analysis.py; \
	else \
	  echo "ruff: not installed; SKIPPED (python lint is CI-only on ruff-less boxes)"; \
	fi

tidy analyze:
	$(MAKE) -C $(NATIVE) $@

# Native builds + unit-test matrix (plain, TSan, ASan+UBSan, UBSan-only).
native check check-tsan check-asan check-ubsan tsan asan ubsan clean:
	$(MAKE) -C $(NATIVE) $(subst native,all,$@)

# Tier-1 test suite, as the driver runs it (ROADMAP.md, Design 8): six
# workers, a file to a worker. On one process it takes over an hour.
test:
	JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python3 -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p xdist -n 6 --dist loadfile -p no:randomly

.PHONY: lint invariants threadroles ruff tidy analyze native check \
        check-tsan check-asan check-ubsan tsan asan ubsan clean test
