# Developer conveniences; see check.sh for the full health check.

.PHONY: test native tsan check perf clean

test:
	python -m pytest tests/ -q

native:
	$(MAKE) -C native test

tsan:
	$(MAKE) -C native test-tsan

check:
	bash check.sh

perf:
	python perf/fir.py --runs 1
	python perf/null.py --runs 1
	python perf/msg.py --runs 1

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
