"""Launch layer: the retrieval serving entry point and the compile cache."""
