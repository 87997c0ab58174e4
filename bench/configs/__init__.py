"""Configurations: ``<name>.json`` (as run), ``<name>.py`` (glue into the
program) and ``<name>_ref.py`` (the plain reference)."""
