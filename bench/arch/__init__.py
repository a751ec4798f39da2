"""Architectures, one module per kind, found by the name a configuration's
file gives under ``architecture.kind``.

A module provides ``program_config(spec)`` (the program's ``ModelConfig``),
``make_weights(spec, key)`` (the parameter tree the program lays out for
it, on the device) and ``shape(raw)`` (an object with ``vocab``,
``projections()`` and ``request_model_flops(prompt_len, decoded)``), and
reads its own keys of the file.  Nothing outside this package and
``bench/reference/`` knows an architecture's keys."""
