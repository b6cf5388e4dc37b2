PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: check check-docs test serve-example dev-deps

# tier-1 gate — run on every PR (see .github/workflows/ci.yml)
check:
	$(PYTHON) -m pytest -x -q

# docs gate: markdown links + the DESIGN.md stable-anchor contract
check-docs:
	$(PYTHON) tools/check_docs.py

test: check

serve-example:
	$(PYTHON) examples/serve_gnn.py

dev-deps:
	$(PYTHON) -m pip install -r requirements-dev.txt
