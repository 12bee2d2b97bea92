"""KWT-Tiny reproduction, grown toward a production-scale jax system."""
