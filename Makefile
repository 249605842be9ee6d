# Dev/CI entry points. CI runs `make ci`.
#
# XLA_FLAGS stays UNSET for the pytest run on purpose: smoke tests must see
# the single real CPU device; tests/test_multidevice.py spawns subprocesses
# that set --xla_force_host_platform_device_count=8 themselves. The `smoke`
# target DOES force 8 host devices so every model family is exercised on a
# multi-device CPU mesh in CI.

PY ?= python
PYTHONPATH := src

.PHONY: test smoke serve-demo ci

test:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m pytest -q

smoke:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	PYTHONPATH=$(PYTHONPATH) $(PY) scripts/dev_smoke.py

serve-demo:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m repro.launch.serve --arch qwen3-8b \
	    --n-requests 6 --prompt-len 24 --max-new 8 \
	    --policy round_robin --tpot-budget-ms 9 --admission shed --trace

ci: smoke test
