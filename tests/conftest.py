"""Imported before any test module.  ``pyproject.toml`` puts ``src`` on the
path; importing ``repro`` here stops the session with one clear error if
the package does not resolve, instead of one per test module."""

import repro  # noqa: F401
