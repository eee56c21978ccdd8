"""Pallas band kernels (``ops``) and their pure-jnp oracles (``ref``)."""
