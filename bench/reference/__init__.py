"""Plain float32 references, one module per architecture, found by the
name a configuration's file gives under ``reference``."""
