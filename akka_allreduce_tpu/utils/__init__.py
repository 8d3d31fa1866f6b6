"""Shared utilities."""

from akka_allreduce_tpu.utils.vma import cast_varying, ensure_varying, \
    psum_all

__all__ = ["cast_varying", "ensure_varying", "psum_all"]
