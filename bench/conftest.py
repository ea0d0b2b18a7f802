"""The benchmark's CPU tests pin the CPU themselves and describe no TPU."""
import jax

jax.config.update("jax_platforms", "cpu")
