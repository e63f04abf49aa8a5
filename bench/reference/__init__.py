"""Plain references, one module per ``model_type`` of a configuration."""
