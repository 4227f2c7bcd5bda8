"""Plain references of the benchmark's models, one module per model,
found by the ``reference`` key of a configuration file."""
