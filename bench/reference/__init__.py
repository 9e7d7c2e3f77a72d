"""Plain float32 references: weights from the seed, the two language
models, and their training steps. Nothing here imports the program."""
