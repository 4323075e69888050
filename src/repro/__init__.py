"""JAX/Pallas reproduction of IDKD decentralized learning."""
