"""Entry points: the train and serve launchers, elastic reshard, meshes,
the compile cache."""
