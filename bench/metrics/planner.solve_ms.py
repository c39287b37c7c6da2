"""Mean host wall of one ``optimizer.solve`` call in the window, in ms."""


def read(rec):
    walls = [p["solve_s"] for p in rec["plans"]]
    return 1e3 * sum(walls) / len(walls) if walls else None
