"""Plain float32 references, one per configuration, importing nothing of
the system under test."""
