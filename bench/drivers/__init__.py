"""One driver per traffic kind (`kind` in a bench/traffic/*.json file)."""
