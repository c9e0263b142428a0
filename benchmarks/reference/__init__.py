"""Plain references: straightforward jax.numpy, float32, no cache, no
kernel, nothing imported from ray_tpu."""
