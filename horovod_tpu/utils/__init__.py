"""Shared utilities (compile cache, device checks, misc tooling)."""
