"""repro: SystolicAttention reproduction + the jax_pallas scale-out stack."""
