# Dev workflow targets (see ROADMAP.md "Dev workflow").
PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-witnessed lint check

test:                 ## tier-1 verify
	PYTHONPATH=$(PYTHONPATH) python -m pytest -x -q

test-witnessed:       ## tier-1 + lock-order witness (latent deadlocks)
	PYTHONPATH=$(PYTHONPATH) python -m pytest -x -q --lockgraph

lint:                 ## repo-invariant linter (tools/analysis), <2s
	python -m tools.analysis.lint

# check = lint + witnessed tier-1 tests (the lock-order and leak
# witnesses ride the test run) — run it before landing anything that
# touches the stack. The chip benchmark is bench/run.py.
check: lint test-witnessed  ## lint + witnessed tests
