"""Modules found by name; see the package docstring."""
