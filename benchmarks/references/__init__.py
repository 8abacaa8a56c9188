"""Plain float32 references, one module per block kind a configuration
file may name under ``reference`` (``kinds/serve_sessions.py`` imports it).
"""
