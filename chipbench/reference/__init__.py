"""Plain float32 references of the benchmark's models and of their training
step, written from the published descriptions and independent of the
program under test.  See ``train.py`` for what is compared."""
