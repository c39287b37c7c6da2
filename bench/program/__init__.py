"""Each model family's sizes as the program's ``ModelConfig``."""
