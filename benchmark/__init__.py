"""The benchmark: see benchmark/README.md.  A regular package, so that
``import benchmark`` means this directory wherever ``tests/benchmark`` is on
the path too."""
