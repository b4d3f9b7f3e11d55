"""Plain references, one file per configuration: jax.numpy float32 at the
highest matmul precision, no kernels, nothing imported from fedml_tpu."""
