"""One import site for ``shard_map`` (``jax.shard_map``, ``check_vma``)."""

from jax import shard_map  # noqa: F401
