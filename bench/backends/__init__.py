"""Serving backends, one file per config kind."""
