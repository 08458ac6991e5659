"""Launchers: mesh construction, training and serving drivers."""
