"""Adapters from a configuration file to the program's model builders:
``bench/models/<model>.py`` for a configuration whose ``model`` is
``<model>``.  Each exposes ``KIND`` (the traffic kind it serves) and
``build_graph(cfg)``."""
