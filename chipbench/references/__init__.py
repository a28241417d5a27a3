"""Plain references, one per model family, found by the configuration's ``reference``."""
