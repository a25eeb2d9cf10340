"""Shared utilities: error classes, quorum reducers, hashing helpers."""
