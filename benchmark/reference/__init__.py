"""Plain references of the benchmark's configurations: jax.numpy,
float32, no kernels, no cache, nothing imported from the program."""
