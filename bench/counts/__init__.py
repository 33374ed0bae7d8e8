"""Each model's work counts, one file per configuration ``reference`` key
(``bench/work.py`` finds them by name)."""
