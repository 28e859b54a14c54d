"""BFS pull Pallas kernel (kernel.py) and its pure-jnp oracle (ref.py)."""
