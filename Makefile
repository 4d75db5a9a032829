# Tier-1 verification: the one command CI and humans both run.
# Collection errors fail loudly here — a missing module kills the whole
# suite at collect time, which is exactly what we want to see first.

PY ?= python

.PHONY: verify test smoke

verify:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} $(PY) -m pytest -x -q

test:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} $(PY) -m pytest -q

smoke:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} $(PY) -m repro.launch.train \
		--arch qwen25_3b --smoke --steps 10 --batch 4 --seq 64
