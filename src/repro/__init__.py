"""repro — SEE-MCAM reproduction + production jax_pallas serving/training stack."""
