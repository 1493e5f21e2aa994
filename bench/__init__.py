"""The chip benchmark: cells of a configuration and a traffic mix,
served through ``ServeEngine``."""
