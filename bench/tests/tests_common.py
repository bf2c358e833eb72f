from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
