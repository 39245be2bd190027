"""The plain references, one module per set of semantics (`bench/check.py`
says what a module declares and brings)."""
