"""The benchmark harness: everything between ``BENCHMARK.json`` and the
program under test (see ``bench/README.md``)."""
