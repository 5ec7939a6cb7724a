"""Launcher: meshes, step functions, compile cache, train/serve drivers."""
